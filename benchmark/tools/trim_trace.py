"""Cut a recorded ``.xplane.pb`` down to what the reduction reads, so
that a trace taken on the chip can be kept beside the tests:

    python benchmark/tools/trim_trace.py <in.xplane.pb> <out.xplane.pb.gz> \
        [--steps N] [--chips N]

Kept: of the first ``--chips`` TPU planes the lines ``Steps``, ``XLA
Modules``, ``XLA Ops`` and ``Async XLA Ops`` within the first
``--steps`` steps, and the host annotations that start with ``bench/``.
Times are as recorded.  An op's name keeps its instruction name, its
opcode and the Mosaic target; shapes and operands, most of a trace's
bytes, become ``...``.
"""

import argparse
import gzip
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import trace_reduce as tr     # noqa: E402

DEVICE_LINES = ('Steps', 'XLA Modules', tr.OP_LINE, tr.ASYNC_LINE)


def short(text):
    parsed = tr.parse_instruction(text)
    if parsed is None:
        return text
    name, opcode = parsed
    target = ', ' + tr.MOSAIC_TARGET if tr.MOSAIC_TARGET in text else ''
    return '%%%s = ... %s(...)%s' % (name, opcode, target)


class Plane(object):
    def __init__(self, name):
        self.name, self.lines, self.ids = name, [], {}

    def add_line(self, name, events):
        """events: [(event name, start ns, duration ns)]"""
        rows = []
        for ev_name, start, dur in events:
            i = self.ids.setdefault(ev_name, len(self.ids) + 1)
            rows.append('events { metadata_id: %d offset_ps: %d '
                        'duration_ps: %d }' % (i, round(start * 1000),
                                               round(dur * 1000)))
        self.lines.append('lines { name: "%s" timestamp_ns: 0\n%s\n}'
                          % (name, '\n'.join(rows)))

    def text(self):
        meta = ['event_metadata { key: %d value { id: %d name: "%s" } }'
                % (i, i, n.replace('\\', '\\\\').replace('"', '\\"'))
                for n, i in self.ids.items()]
        return 'planes { name: "%s"\n%s\n%s\n}' % (
            self.name, '\n'.join(self.lines), '\n'.join(meta))


def trim(profile, steps, chips):
    planes = []
    for ordinal, plane in sorted(tr.device_planes(profile).items())[:chips]:
        lines = {line.name: list(line.events) for line in plane.lines}
        kept = sorted(lines['Steps'], key=lambda e: e.start_ns)[:steps]
        lo = kept[0].start_ns
        hi = kept[-1].start_ns + kept[-1].duration_ns
        out = Plane(plane.name)
        for name in DEVICE_LINES:
            out.add_line(name, [
                (short(e.name), e.start_ns, e.duration_ns)
                for e in lines.get(name, ()) if lo <= e.start_ns <= hi])
        planes.append(out)
    host = Plane('/host:CPU')
    host.add_line('benchmark', [(s.name, s.start, s.end - s.start)
                                for s in tr.host_spans(profile, 'bench/')])
    return '\n'.join(p.text() for p in planes + [host])


def main():
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('source')
    ap.add_argument('target')
    ap.add_argument('--steps', type=int, default=1)
    ap.add_argument('--chips', type=int, default=1)
    args = ap.parse_args()
    from jax.profiler import ProfileData
    text = trim(tr.load(args.source), args.steps, args.chips)
    with gzip.open(args.target, 'wb') as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    print('%s: %d bytes' % (args.target, os.path.getsize(args.target)))


if __name__ == '__main__':
    main()
