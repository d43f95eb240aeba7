"""From a profiler trace to numbers: the one reduction every PR's
per-layer metrics go through.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` (nothing but JAX; no TensorBoard, no
perfetto JSON).  On a TPU each chip is a plane ``/device:TPU:<n>``
whose line ``XLA Ops`` holds one event per executed HLO operation,
named with the instruction's whole text; control flow nests (a
``while`` or ``conditional`` event covers its body's events), so
durations are never summed.  The line ``Async XLA Ops`` holds what runs
beside the ops (DMA copies, and collectives from -start to -done); it
is read for the collectives' spans only:

- *busy* is the union of the op intervals;
- every instant is given to the innermost op running then (the one
  that started last), and that op's kind decides whether the instant
  counts as a Mosaic kernel, a collective or any other XLA op;
- *exposed* collective time is collective time during which no op of
  another kind runs on that device;
- *idle* is the window minus busy; the window runs from the first op's
  start to the last op's end over the chips used.

Run ``python benchmark/lib/trace_reduce.py <file.xplane.pb>`` to look
at a trace by hand: planes, lines, kinds and the longest operations.
"""

import collections
import glob
import heapq
import os
import re

DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
OP_LINE = 'XLA Ops'
# spans of operations that run beside the op line: DMA copies and
# slices, and collectives from their -start to their -done
ASYNC_LINE = 'Async XLA Ops'

MOSAIC, COLLECTIVE, OTHER = 'mosaic', 'collective', 'other'
KINDS = (MOSAIC, COLLECTIVE, OTHER)

# HLO opcodes that move data between chips
COLLECTIVE_OPCODES = (
    'all-reduce', 'all-gather', 'reduce-scatter', 'all-to-all',
    'collective-permute', 'collective-broadcast', 'ragged-all-to-all',
    'send', 'recv')
_COLLECTIVE = '(%s)(-start|-done)?' % '|'.join(COLLECTIVE_OPCODES)
# a TPU trace names an op event with its whole HLO instruction,
# "%<name> = <shape> <opcode>(<operands>), <attributes>"
_INSTRUCTION = re.compile(r'^%?(\S+) = ')
_COLLECTIVE_OPCODE = re.compile(r'^%s$' % _COLLECTIVE)
# other traces print the instruction's name alone, "<opcode>[.<n>]"
_COLLECTIVE_NAME = re.compile(r'^%s(\.\d+)?$' % _COLLECTIVE)
# a Mosaic (Pallas) kernel is the custom call with this target
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'

Op = collections.namedtuple('Op', 'name start end kind')
Span = collections.namedtuple('Span', 'name start end')


def newest_xplane(logdir):
    """The trace a ``jax.profiler.start_trace(logdir)`` session wrote."""
    found = sorted(glob.glob(os.path.join(
        logdir, 'plugins', 'profile', '*', '*.xplane.pb')),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError('no .xplane.pb under %s' % logdir)
    return found[-1]


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def parse_instruction(text):
    """(name, opcode) of an HLO instruction's text, or None where the
    text is not one.  The shape between " = " and the opcode is one
    word, or a tuple in parentheses that holds spaces."""
    m = _INSTRUCTION.match(text)
    if not m:
        return None
    i = m.end()
    if text[i:i + 1] == '(':
        depth = 0
        for i in range(i, len(text)):
            depth += (text[i] == '(') - (text[i] == ')')
            if depth == 0:
                break
    rest = text[i:].partition(' ')[2]
    return m.group(1), rest.partition('(')[0]


def parse_op(text):
    """(instruction name, kind) of an op event from the name the trace
    gives it.  The instruction's name is stable across runs of one
    program and carries the framework's name for a custom call
    (``jvp_fused_multihead_attention_.12``: the executor lowers every
    fluid op inside a named scope of its type)."""
    parsed = parse_instruction(text)
    if parsed is None:              # the instruction's name alone
        if _COLLECTIVE_NAME.match(text):
            return text, COLLECTIVE
        return text, MOSAIC if text.startswith('custom-call') else OTHER
    name, opcode = parsed
    if opcode == 'custom-call' and MOSAIC_TARGET in text:
        return name, MOSAIC
    if opcode.startswith('async-'):     # a wrapper named after what it wraps
        return name, COLLECTIVE if _COLLECTIVE_NAME.match(name) else OTHER
    return name, COLLECTIVE if _COLLECTIVE_OPCODE.match(opcode) else OTHER


def plane_ops(plane, line_name=OP_LINE):
    """Every event of the plane's line of that name as an Op, times in
    ns."""
    ops = []
    for line in plane.lines:
        if line.name != line_name:
            continue
        for ev in line.events:
            start = float(ev.start_ns)
            name, kind = parse_op(ev.name)
            ops.append(Op(name, start, start + float(ev.duration_ns), kind))
    return ops


def device_planes(profile):
    """{chip ordinal: plane} of the planes that are TPU chips."""
    out = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            out[int(m.group(1))] = plane
    return out


def host_spans(profile, prefix):
    """The host annotations whose name starts with ``prefix``, from
    every plane that is not a device."""
    spans = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    start = float(ev.start_ns)
                    spans.append(Span(ev.name, start,
                                      start + float(ev.duration_ns)))
    return sorted(spans, key=lambda s: s.start)


# ------------------------------------------------------------ intervals
def union(intervals):
    """Merged, sorted, non-overlapping [(start, end)]."""
    out = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(merged):
    return sum(b - a for a, b in merged)


def subtract(merged_a, merged_b):
    """The parts of ``merged_a`` that ``merged_b`` does not cover (both
    as ``union`` returns them)."""
    out, j = [], 0
    for a, b in merged_a:
        while j < len(merged_b) and merged_b[j][1] <= a:
            j += 1
        k, cursor = j, a
        while k < len(merged_b) and merged_b[k][0] < b:
            if merged_b[k][0] > cursor:
                out.append((cursor, merged_b[k][0]))
            cursor = max(cursor, merged_b[k][1])
            k += 1
        if cursor < b:
            out.append((cursor, b))
    return out


def innermost_segments(ops):
    """[(start, end, op)]: the timeline cut at every op boundary, each
    piece given to the op running then that started last (the deepest
    of a nest).  Pieces in which nothing runs are left out."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    bounds = sorted({o.start for o in ops} | {o.end for o in ops})
    active, segments, nxt = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while nxt < len(ops) and ops[nxt].start <= a:
            o = ops[nxt]
            heapq.heappush(active, (-o.start, o.end - o.start, nxt, o))
            nxt += 1
        while active and active[0][3].end <= a:
            heapq.heappop(active)
        if not active:
            continue
        owner = active[0][3]
        if segments and segments[-1][2] is owner and \
                segments[-1][1] == a:
            segments[-1] = (segments[-1][0], b, owner)
        else:
            segments.append((a, b, owner))
    return segments


# ------------------------------------------------------------ reduction
class DeviceTimeline(object):
    """One chip's ops reduced: ``busy`` (merged intervals), the time of
    each kind by innermost op, and the self time of each op name."""

    def __init__(self, ops, async_ops=()):
        self.ops = ops
        self.async_collectives = [o for o in async_ops
                                  if o.kind == COLLECTIVE]
        self.segments = innermost_segments(ops)
        self.busy = union((o.start, o.end) for o in ops)
        self.kind_intervals = {k: [] for k in KINDS}
        self.self_ns = collections.Counter()
        self.self_kind = {}
        for a, b, op in self.segments:
            self.kind_intervals[op.kind].append((a, b))
            self.self_ns[op.name] += b - a
            self.self_kind[op.name] = op.kind
        self.kind_intervals = {k: union(v) for k, v in
                               self.kind_intervals.items()}

    @property
    def start(self):
        return self.busy[0][0]

    @property
    def end(self):
        return self.busy[-1][1]

    def kind_ns(self, kind):
        return length(self.kind_intervals[kind])

    def exposed_collective_ns(self):
        """Collective time during which no op of another kind runs on
        this chip: the collectives' whole intervals, on the op line
        and from -start to -done on the async line, minus where
        another kind is the innermost op."""
        whole = union((o.start, o.end)
                      for o in self.ops + self.async_collectives
                      if o.kind == COLLECTIVE)
        others = union(self.kind_intervals[MOSAIC] +
                       self.kind_intervals[OTHER])
        return length(subtract(whole, others))

    def matching_ns(self, pattern, kind):
        """Innermost time, in ns, of the ops of this kind whose name
        matches the regular expression."""
        rx = re.compile(pattern)
        return sum(b - a for a, b, op in self.segments
                   if op.kind == kind and rx.search(op.name))


class Reduced(object):
    """A whole trace reduced.  ``devices``: {ordinal: DeviceTimeline}
    for the chips that ran an op; ``spans``: the benchmark's own host
    annotations; ``steps``: how many steps the traced window held."""

    def __init__(self, devices, spans, steps):
        self.devices = devices
        self.spans = spans
        self.steps = steps

    @property
    def first(self):
        """The chip per-layer metrics are read on: the lowest ordinal."""
        return self.devices[min(self.devices)]

    @property
    def window_ns(self):
        return (max(d.end for d in self.devices.values()) -
                min(d.start for d in self.devices.values()))

    @property
    def busy_ns(self):
        """Averaged over the chips used."""
        return sum(length(d.busy) for d in self.devices.values()) / \
            len(self.devices)

    def per_step_ms(self, ns):
        return ns / 1e6 / self.steps


def reduce_profile(profile, steps, span_prefix='bench/'):
    """Reduced, or None where the trace holds no device op."""
    devices = {}
    for ordinal, plane in device_planes(profile).items():
        ops = plane_ops(plane)
        if ops:
            devices[ordinal] = DeviceTimeline(
                ops, plane_ops(plane, ASYNC_LINE))
    if not devices:
        return None
    return Reduced(devices, host_spans(profile, span_prefix), steps)


def top_ops(timeline, n=10):
    """[[name, seconds]] of the ops with most innermost time, under the
    names the trace prints."""
    return [[name, ns / 1e9]
            for name, ns in timeline.self_ns.most_common(n)]


def idle_gaps(timeline, spans, n=5):
    """[[label, seconds]] of the longest gaps between this chip's ops,
    each labelled with the shortest host span that covers the gap's
    middle ('no span' where none does)."""
    gaps = sorted(((b2 - a1, a1, b2) for (_, a1), (b2, _) in
                   zip(timeline.busy, timeline.busy[1:])),
                  reverse=True)[:n]
    out = []
    for dur, a, b in gaps:
        mid = (a + b) / 2
        covering = [s for s in spans if s.start <= mid <= s.end]
        label = min(covering, key=lambda s: s.end - s.start).name \
            if covering else 'no span'
        out.append([label, dur / 1e9])
    return out


def describe(profile, top=25):
    """Lines a person reads before writing code against a trace."""
    lines = []
    for plane in profile.planes:
        lines.append('PLANE %s' % plane.name)
        for line in plane.lines:
            events = list(line.events)
            lines.append('  LINE %-28s %7d events' %
                         (line.name, len(events)))
            if DEVICE_PLANE.match(plane.name) and events:
                sample = events[len(events) // 2]
                lines.append('    e.g. %s %s' %
                             (sample.name[:300], dict(sample.stats)))
    reduced = reduce_profile(profile, steps=1)
    if reduced is None:
        return lines + ['no device op in this trace']
    for ordinal, dev in sorted(reduced.devices.items()):
        lines.append('DEVICE %d: window %.3f ms, busy %.3f ms; %s' % (
            ordinal, (dev.end - dev.start) / 1e6, length(dev.busy) / 1e6,
            ', '.join('%s %.3f ms' % (k, dev.kind_ns(k) / 1e6)
                      for k in KINDS)))
        lines.append('  exposed collective %.3f ms'
                     % (dev.exposed_collective_ns() / 1e6))
        for name, ns in dev.self_ns.most_common(top):
            lines.append('  %10.3f ms  %-10s %s' % (
                ns / 1e6, dev.self_kind[name], name))
    return lines


if __name__ == '__main__':
    import sys
    print('\n'.join(describe(load(sys.argv[1]))))
