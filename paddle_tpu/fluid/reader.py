"""DataLoader. Reference: python/paddle/fluid/reader.py —
DataLoader.from_generator(:75) feeding a LoDTensorBlockingQueue(:298),
DataLoader.from_dataset(:261) over the Dataset runtime, double-buffered
to the device by operators/reader/buffered_reader.cc.

TPU-native async pipeline: a background thread drains the user
generator into a bounded queue (`capacity` — the LoDTensorBlockingQueue
analog) and, with use_double_buffer, stages each batch onto the device
with jax.device_put as it is enqueued.  device_put returns immediately
(the H2D DMA runs behind the XLA stream), so the NEXT batch's transfer
overlaps the CURRENT step's compute — buffered_reader's double buffer
without a dedicated stream API.

The LoD-replacement front-end lives here too: BucketedGeneratorLoader
groups genuinely ragged samples into a small set of padded shapes
("length bucketing"), so XLA compiles ONE executable per bucket —
bounded recompiles where the reference used LoD offset vectors
(framework/lod_tensor.h:219, operators/math/sequence_padding.h).
"""

import queue as _queue
import threading
import time as _time

import numpy as np

from . import core
from . import monitor
from . import trace as _trace


class _AsyncBatchIterator(object):
    """Background-thread prefetch over a batch generator: the
    LoDTensorBlockingQueue + buffered_reader pair.

    The HOST queue holds up to `capacity` numpy batches (the blocking
    queue); the DEVICE window stages only `stage_depth` (default 2,
    buffered_reader.cc's depth) of them onto `device` with
    jax.device_put — so capacity bounds host memory, not HBM.  Staging
    happens in the consumer's next(): jit dispatch is async, so the
    device_put DMA for batch N+1/N+2 overlaps batch N's compute.

    Producer exceptions re-raise at the consumer's next(); exhaustion
    is sticky (every later next() raises StopIteration again); close()
    (or GC) stops the producer without draining the generator."""

    _END = object()

    def __init__(self, gen, capacity, device=None, stage_depth=2,
                 stage_exclude=()):
        self._q = _queue.Queue(maxsize=max(1, int(capacity)))
        self._stop = threading.Event()
        self._exc = None
        self._device = device
        self._stage_exclude = frozenset(stage_exclude)
        self._staged = []
        self._stage_depth = max(1, int(stage_depth))
        self._done = False
        self._thread = threading.Thread(
            target=self._work, args=(gen,), daemon=True)
        self._thread.start()

    def _put(self, item):
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except _queue.Full:
                continue
        return False

    def _work(self, gen):
        try:
            for batch in gen():
                if not self._put(batch):
                    return
                # producer-side accounting (LoDTensorBlockingQueue
                # stats analog): batches entering the host queue, and
                # its depth right after the put
                monitor.add('reader/batches_produced')
                monitor.set_gauge('reader/queue_depth', self._q.qsize())
        except BaseException as e:  # noqa: B036 — must cross threads
            self._exc = e
        finally:
            self._put(self._END)

    def _stage(self, batch):
        if self._device is None:
            return batch
        import jax
        out = {}
        host_part = None
        nbytes = 0.0
        for k, v in batch.items():
            if k in self._stage_exclude:
                out[k] = v
                continue
            if isinstance(v, core.LoDTensor):
                v = v.data
            if isinstance(v, (np.ndarray, np.generic)) or not hasattr(
                    v, 'devices'):
                v = np.asarray(v)
                nbytes += float(v.nbytes)
                if host_part is None:
                    host_part = {}
                host_part[k] = v
                continue
            out[k] = v
        if host_part:
            # ONE device_put over the whole batch: a single async H2D
            # submission instead of one python round-trip per field.
            # These buffers are NOT marked donation-owned: the batch
            # dict is handed to the CALLER (who may hold or re-feed
            # it), so the executor must keep its defensive copy if one
            # of these ever binds to a donated state slot.
            monitor.add('reader/bytes_staged', nbytes)
            with _trace.span('reader_h2d', nbytes=nbytes):
                out.update(jax.device_put(host_part, self._device))
        return out

    def _fill_window(self):
        while not self._done and len(self._staged) < self._stage_depth:
            if self._staged:
                # window non-empty: only top up opportunistically, a
                # slow producer must not block the consumer here
                try:
                    item = self._q.get_nowait()
                except _queue.Empty:
                    return
            else:
                # empty device window: the consumer now stalls on the
                # producer — the time the step loop loses to input.
                # A healthy pipeline keeps this histogram's sum near 0
                t0 = _time.perf_counter()
                item = self._q.get()
                t1 = _time.perf_counter()
                monitor.observe('reader/consume_blocked_seconds',
                                t1 - t0)
                _trace.record('reader_wait', t0, t1)
            if item is self._END:
                self._done = True
                self._stop.set()
                return
            self._staged.append(self._stage(item))

    def __iter__(self):
        return self

    def __next__(self):
        self._fill_window()
        if not self._staged:
            if self._exc is not None:
                exc, self._exc = self._exc, None
                raise exc
            raise StopIteration
        batch = self._staged.pop(0)
        monitor.add('reader/batches_consumed')
        monitor.set_gauge('reader/queue_depth', self._q.qsize())
        self._fill_window()  # keep the DMA window ahead of compute
        return batch

    next = __next__

    def close(self):
        self._stop.set()
        self._done = True
        self._staged = []
        # unblock a producer parked on a full queue
        try:
            while True:
                self._q.get_nowait()
        except _queue.Empty:
            pass

    def __del__(self):  # best effort
        try:
            self.close()
        except Exception:
            pass




def pow2_bucket_ladder(max_size, start=1):
    """Power-of-two bucket boundaries covering sizes up to `max_size`:
    [start, 2*start, ...] ending at the first power >= max_size.  The
    ladder the bucketed loader applies to sequence LENGTHS and the
    serving plane applies to BATCH rows — one AOT executable per rung,
    O(log max) executables total."""
    out = []
    b = max(1, int(start))
    top = max(1, int(max_size))
    while b < top:
        out.append(b)
        b *= 2
    out.append(b)
    return out


def bucket_for(size, boundaries):
    """The smallest boundary >= `size` (the BucketedGeneratorLoader
    rule, shared with fluid.serving's batch coalescer).  `boundaries`
    must be sorted ascending."""
    for b in boundaries:
        if size <= b:
            return int(b)
    raise ValueError(
        'size %d exceeds the largest bucket boundary %d'
        % (size, boundaries[-1]))


def mask_name(name, mask_map=None):
    """The '@MASK' companion-feed convention: the mask feed name for a
    padded field (sequence ops consume it as their Mask input; the
    serving plane emits row masks under the same names)."""
    if mask_map:
        return mask_map.get(name, name + '@MASK')
    return name + '@MASK'


class DataLoader(object):
    @staticmethod
    def from_generator(feed_list=None, capacity=64, use_double_buffer=True,
                       iterable=True, return_list=False,
                       use_multiprocess=False, bucket_boundaries=None,
                       batch_size=None, mask_map=None, drop_last=False,
                       ragged_fields=None, stage_exclude=None):
        """bucket_boundaries + batch_size turn the loader into the
        bucketing front-end for variable-length data (see
        BucketedGeneratorLoader)."""
        if bucket_boundaries is not None:
            if not batch_size:
                raise ValueError('bucketed DataLoader needs batch_size')
            return BucketedGeneratorLoader(
                feed_list, bucket_boundaries, batch_size,
                mask_map=mask_map, drop_last=drop_last,
                capacity=capacity, iterable=iterable,
                ragged_fields=ragged_fields,
                use_double_buffer=use_double_buffer,
                stage_exclude=stage_exclude)
        return GeneratorLoader(feed_list, capacity, iterable,
                               use_double_buffer=use_double_buffer,
                               stage_exclude=stage_exclude)

    @staticmethod
    def from_dataset(dataset, places, drop_last=True):
        """Iterate the Dataset runtime's batches (reference
        reader.py:261 DatasetLoader over the C++ Trainer pipeline; here
        the native feeder inside fluid.dataset does the file IO)."""
        return DatasetLoader(dataset, places, drop_last)


class DatasetLoader(object):
    """Reference: reader.py:261 — iterable view over a
    fluid.DatasetFactory dataset (QueueDataset/InMemoryDataset)."""

    def __init__(self, dataset, places, drop_last=True):
        self._dataset = dataset
        self._places = places
        self._drop_last = drop_last

    def _batches(self):
        full = None
        for feed in self._dataset.batches():
            if self._drop_last:
                n = min(np.asarray(v).shape[0] for v in feed.values())
                if full is None:
                    full = n
                elif n < full:
                    continue  # short tail batch: shape-stable training
            yield feed

    def __iter__(self):
        return iter(self._batches())

    def start(self):
        self._iter = iter(self._batches())

    def next(self):
        return next(self._iter)

    def reset(self):
        self._iter = iter(self._batches())


class GeneratorLoader(object):
    def __init__(self, feed_list, capacity=64, iterable=True,
                 use_double_buffer=True, stage_exclude=None):
        """stage_exclude: feed names the double buffer must NOT
        device_put — fields consumed only by HOST ops (PS sparse-id
        lookups etc.); staging those would ship them to the device and
        pull them straight back per step (two extra transfers)."""
        self._feed_list = feed_list or []
        self._capacity = capacity
        self._iterable = iterable
        self._use_double_buffer = use_double_buffer
        self._stage_exclude = frozenset(stage_exclude or ())
        self._generator = None
        self._places = None
        self._iter = None

    def _target_device(self):
        """Device the double buffer stages onto (first place passed to
        set_*_generator, else device 0)."""
        if not self._use_double_buffer:
            return None
        place = self._places[0] if isinstance(
            self._places, (list, tuple)) and self._places else \
            (self._places or core.XLAPlace(0))
        try:
            return place.jax_device()
        except Exception:
            import jax
            return jax.devices()[0]

    def set_sample_generator(self, reader, batch_size, drop_last=True,
                             places=None):
        def batched():
            batch = []
            for sample in reader():
                batch.append(sample)
                if len(batch) == batch_size:
                    yield batch
                    batch = []
            if batch and not drop_last:
                yield batch
        return self.set_sample_list_generator(batched, places)

    def set_sample_list_generator(self, reader, places=None):
        from .data_feeder import DataFeeder
        self._places = places
        place = places[0] if isinstance(places, (list, tuple)) else \
            (places or core.XLAPlace(0))
        feeder = DataFeeder(self._feed_list, place)

        def gen():
            for batch in reader():
                yield feeder.feed(batch)
        self._generator = gen
        return self

    def set_batch_generator(self, reader, places=None):
        self._places = places

        def gen():
            for batch in reader():
                if isinstance(batch, dict):
                    yield batch
                else:
                    yield {v.name: np.asarray(a)
                           for v, a in zip(self._feed_list, batch)}
        self._generator = gen
        return self

    def _make_iter(self):
        if self._generator is None:
            raise RuntimeError('DataLoader: call set_*_generator first')
        # one live prefetch pipeline per loader: an abandoned earlier
        # iteration (early break) is closed here so its thread and
        # device-staged batches don't linger until GC
        prev = getattr(self, '_live_iter', None)
        if prev is not None:
            prev.close()
        it = _AsyncBatchIterator(self._generator, self._capacity,
                                 self._target_device(),
                                 stage_exclude=self._stage_exclude)
        self._live_iter = it
        return it

    def __iter__(self):
        return self._make_iter()

    def start(self):
        self._iter = self._make_iter()

    def next(self):
        return next(self._iter)

    def reset(self):
        if self._iter is not None:
            self._iter.close()
        self._iter = self._make_iter()




class BucketedGeneratorLoader(GeneratorLoader):
    """Length-bucketing loader for genuinely ragged samples.

    Each sample is a tuple aligned with feed_list; ragged fields
    (feed vars with lod_level > 0, or any field whose value is a
    variable-length sequence) are padded to the sample's bucket
    boundary — the smallest boundary >= the sample's longest ragged
    field.  Batches are emitted per bucket once batch_size samples of
    that bucket accumulate, so the executor sees at most
    len(bucket_boundaries) distinct shapes and jax.jit caches one
    executable per bucket (the recompile bound the reference got from
    LoD + sequence_padding kernels).

    For every ragged field a float mask [B, T] is emitted under
    mask_map[name] (default '<name>@MASK' — feed vars with those names
    pick it up; sequence ops consume it as their Mask input).
    """

    def __init__(self, feed_list, bucket_boundaries, batch_size,
                 mask_map=None, drop_last=False, capacity=64,
                 iterable=True, ragged_fields=None,
                 use_double_buffer=True, stage_exclude=None):
        super(BucketedGeneratorLoader, self).__init__(
            feed_list, capacity, iterable,
            use_double_buffer=use_double_buffer,
            stage_exclude=stage_exclude)
        self.boundaries = sorted(int(b) for b in bucket_boundaries)
        self.batch_size = batch_size
        self.drop_last = drop_last
        self._mask_map = dict(mask_map or {})
        if ragged_fields is None:
            self._ragged = [getattr(v, 'lod_level', 0) > 0
                            for v in self._feed_list]
        else:
            ragged_fields = set(ragged_fields)
            self._ragged = [v.name in ragged_fields
                            for v in self._feed_list]
        if not any(self._ragged):
            raise ValueError(
                'bucketed DataLoader: no ragged fields — mark feed vars '
                'with lod_level>0 or pass ragged_fields=[names]')

    def _bucket_of(self, length):
        try:
            return bucket_for(length, self.boundaries)
        except ValueError:
            raise ValueError(
                'sample length %d exceeds the largest bucket boundary '
                '%d' % (length, self.boundaries[-1]))

    def _mask_name(self, var):
        return mask_name(var.name, self._mask_map)

    def _pad_batch(self, samples, boundary):
        out = {}
        for i, var in enumerate(self._feed_list):
            col = [s[i] for s in samples]
            if not self._ragged[i]:
                out[var.name] = np.asarray(col)
                continue
            dtype = core.convert_dtype(var.dtype)
            first = np.asarray(col[0])
            tail_shape = first.shape[1:]
            b = len(col)
            padded = np.zeros((b, boundary) + tail_shape, dtype)
            mask = np.zeros((b, boundary), 'float32')
            for r, seq in enumerate(col):
                seq = np.asarray(seq, dtype)
                padded[r, :len(seq)] = seq
                mask[r, :len(seq)] = 1.0
            out[var.name] = padded
            out[self._mask_name(var)] = mask
        return out

    def set_sample_list_generator(self, reader, places=None):
        raise NotImplementedError(
            'bucketed DataLoader consumes per-SAMPLE generators (it '
            'forms the batches itself, one bucket at a time): use '
            'set_sample_generator')

    def set_batch_generator(self, reader, places=None):
        raise NotImplementedError(
            'bucketed DataLoader consumes per-SAMPLE generators (it '
            'forms the batches itself, one bucket at a time): use '
            'set_sample_generator')

    def set_sample_generator(self, reader, batch_size=None,
                             drop_last=None, places=None):
        if batch_size is not None:
            self.batch_size = batch_size
        if drop_last is not None:
            self.drop_last = drop_last

        def gen():
            buckets = {b: [] for b in self.boundaries}
            for sample in reader():
                longest = max(
                    len(np.asarray(sample[i]))
                    for i in range(len(self._feed_list))
                    if self._ragged[i])
                b = self._bucket_of(longest)
                buckets[b].append(sample)
                if len(buckets[b]) == self.batch_size:
                    yield self._pad_batch(buckets[b], b)
                    buckets[b] = []
            if not self.drop_last:
                for b, rest in buckets.items():
                    if rest:
                        yield self._pad_batch(rest, b)
        self._generator = gen
        return self


class PyReader(GeneratorLoader):
    """Reference: python/paddle/fluid/reader.py:588 PyReader — the
    legacy decorate_* reader surface over the GeneratorLoader path
    (the C++ LoDTensorBlockingQueue is replaced by the native feeder)."""

    def __init__(self, feed_list=None, capacity=64, use_double_buffer=True,
                 iterable=True, return_list=False):
        super(PyReader, self).__init__(
            feed_list, capacity, iterable,
            use_double_buffer=use_double_buffer)
        self._return_list = return_list
        self._started = False

    # decorate_* aliases (reference PyReader API)
    def decorate_sample_generator(self, sample_generator, batch_size,
                                  drop_last=True, places=None):
        return self.set_sample_generator(sample_generator, batch_size,
                                         drop_last, places)

    def decorate_sample_list_generator(self, reader, places=None):
        return self.set_sample_list_generator(reader, places)

    def decorate_batch_generator(self, reader, places=None):
        return self.set_batch_generator(reader, places)

    @property
    def feed_vars(self):
        return self._feed_list

    def start(self):
        self._started = True
        self._iter = self._make_iter()

    def reset(self):
        self._started = False
        if self._iter is not None:
            self._iter.close()
        self._iter = None

    def next(self):
        if not self._started:
            raise RuntimeError('call PyReader.start() first')
        try:
            return next(self._iter)
        except StopIteration:
            self.reset()
            raise
