"""Shared plumbing for the Pallas kernel library: the platform probe,
VMEM residency model and block-size clamp factored out of
flash_attention.py, plus the auto-dispatch decision layer every fused
kernel routes through.

The dispatch contract ("never loses"): a fused kernel runs only when
its enabling flag is on AND every gate it declares holds (on a TPU
device, shapes at/above the kernel's floor, VMEM estimate under
budget, supported dtypes/layout).  Any failed gate falls back to the
kernel's dense JAX reference — bit-identical semantics off-TPU, so
tier-1 runs on the CPU mesh untouched.  ``FLAGS_pallas_force``
promotes the fused path in interpret mode off-TPU; parity tests and
the bench A/B arms use it to exercise the kernel bodies on CPU.

Every decision is observable (the "silent dense fallback" bugfix):
``dispatch()`` bumps ``pallas/<kernel>/dispatch_{fused,dense}`` and,
for dense, ``pallas/<kernel>/fallback/<reason>`` in fluid.monitor and
records the last decision per kernel for /statusz — an A/B arm whose
"fused" side silently ran dense can't masquerade as a fused win.

Decisions happen at TRACE time (lowerings run once per compiled
segment), so none of this is hot-path.
"""

import jax

# The budget the block-size clamp (block_sizes) and the resident-row
# rule (scoped_vmem) work to: what a forward, dq or dkv instance may
# count, by vmem_estimate(), and still compile at Mosaic's DEFAULT
# scoped limit with the pipeline's second buffers and the compiler's
# temporaries beside it.  A figure about that default, not about the
# chip: a v5e core has 128 MiB of VMEM.
VMEM_BUDGET_BYTES = 10 * 1024 * 1024
# Mosaic's default scoped-VMEM limit for one kernel instance on a v5e
# (16 MiB of the core's 128): a kernel that needs more is refused by
# the compiler UNLESS its call asks for more (``vmem_limit_bytes``),
# which scoped_vmem and one_pass_backward_limit do.
SCOPED_VMEM_BYTES = 16 * 1024 * 1024
# The most any call here asks Mosaic for, of the core's 128 MiB, and
# what a call that asks adds to its count for the tiles' temporaries.
VMEM_LIMIT_CAP_BYTES = 100 << 20
VMEM_HEADROOM_BYTES = 16 << 20

# kernel-library registry: name -> descriptor.  Populated by each
# kernel module at import via register_kernel(); tools/check_kernels.py
# walks it to assert every kernel declares a dense fallback.
# GIL-disciplined like fluid.monitor (import-time + trace-time writes
# of scalar values only — no torn composite reads possible).
KERNELS = {}

# last dispatch decision per kernel (bounded by kernel count):
# name -> {'path', 'reason', 'interpret'}
_LAST = {}

_FALLBACK_REASONS = ('flag_off', 'off_tpu', 'below_floor',
                     'vmem_over_budget', 'dtype', 'layout',
                     'auto_partitioned', 'batch_not_split')


def register_kernel(name, dense_fallback, has_vjp=False, doc='',
                    op_types=()):
    """Declare a kernel in the library.  ``dense_fallback`` names the
    dense JAX reference the dispatch layer falls back to (a function
    path string — documentation + check_kernels assertion, not a
    callable, so registration never imports lowering code).
    ``op_types`` names the fluid op types the kernel's fused launch
    subsumes: documentation, nothing reads it."""
    if not dense_fallback:
        raise ValueError('pallas kernel %r must declare its dense '
                         'fallback' % (name,))
    KERNELS[name] = {'dense_fallback': dense_fallback,
                     'has_vjp': bool(has_vjp), 'doc': doc,
                     'op_types': tuple(op_types)}
    return name


def kernels():
    return dict(KERNELS)


def on_tpu():
    """True iff JAX's default backend is a TPU.  Raises what JAX
    raises when no backend comes up: a program that cannot reach its
    chip must say so, not be handed the dense path."""
    return jax.devices()[0].platform == 'tpu'


def force_fused():
    from ...fluid.flags import get_flag
    return bool(get_flag('FLAGS_pallas_force', False))


def score_tile_bytes(block_q, block_k):
    """The f32 p/s score block of one [block_q, block_k] tile plus its
    exp/corr temporaries (-> x3)."""
    return 3 * block_q * block_k * 4


def resident_row_bytes(t, d, itemsize, dv=None):
    """The full rows a forward, dq or dkv instance keeps resident: K
    and V (or Q and dO), ``d`` and ``dv`` wide (one width: dv=None)."""
    return t * (d + (d if dv is None else dv)) * itemsize


def vmem_estimate(t, d, block_q, block_k, itemsize, dv=None):
    """Bytes a kernel instance keeps resident in VMEM.  Dominant terms
    across the three kernels: the full K and V rows (streamed via
    dslice but block-spec'd whole), the q/o/do row blocks, and one
    score tile.  ``d`` is the width of q and k, ``dv`` that of v, o
    and do where it differs."""
    kv = resident_row_bytes(t, d, itemsize, dv)
    rows = block_q * (d + 2 * (d if dv is None else dv)) * itemsize
    return kv + rows + score_tile_bytes(block_q, block_k) + (1 << 18)


def room_for_second_tile(resident, block_q, block_k, itemsize,
                         limit=None, heads=1):
    """May a kernel instance hold two score tiles alive at once (of
    each of the ``heads`` it holds: a d64 pair's instance runs one
    chain a head, so two of its tiles are alive anyway and the
    question is about four)?
    Inside one tile the products and the vector chain depend on each
    other, so an instance that holds one tile runs MXU and VPU in
    turn; with a second tile alive the scheduler puts one's chain
    beside the other's products (PERF.md section 6, PR 29).  Each of
    the two is counted as vmem_estimate() counts the one, and f32
    operands twice (their full-precision products split each operand
    into bf16 parts that sit beside it); ``resident`` is the caller's
    estimate of everything else the instance holds, counted twice
    (the pipeline keeps two buffers of every row and block, which the
    one-tile estimates leave to their budget's headroom); the sum has
    to stay under what the compiler allows: ``limit``, the scoped VMEM
    the call asks Mosaic for, or its default where the call asks for
    nothing (None).
    tests/test_chip_compile.py compiles the shapes that decide."""
    return two_tiles_vmem(resident, block_q, block_k, itemsize, heads) \
        <= (limit or SCOPED_VMEM_BYTES)


def two_tiles_vmem(resident, block_q, block_k, itemsize, heads=1):
    """What room_for_second_tile() weighs against the limit: the bytes
    an instance holds with two score tiles of each of its ``heads``
    alive.  The forward of a d64 pair asks Mosaic for this much
    (one_pass_backward_limit), as its one-pass backward asks for its
    count; every other forward asks by scoped_vmem's older rule."""
    tile = score_tile_bytes(block_q, block_k) * itemsize // 2
    return 2 * resident + 2 * heads * tile


def block_sizes(t, block_q, block_k, d=64, itemsize=2, dv=None, tk=None):
    """Clamp requested blocks to divide t AND fit the VMEM budget —
    an oversized config degrades to the largest fitting one instead of
    failing to compile (round-3's 2048-wide failure mode).  ``dv``:
    vmem_estimate()'s.  ``tk``: the keys' length where it is not the
    queries' (a coarse mask's summaries): block_k divides it, and the
    resident rows are counted at the longer of the two (the dkv call
    keeps Q and dO resident, the others K and V).

    Where the resident K/V rows alone pass the budget at the smallest
    blocks (float32 rows of an 8k sequence at 192 + 128: 10.5 MB), no
    block size helps: the blocks are clamped as if those rows were
    free, and the calls ask Mosaic for the scoped VMEM they need
    (scoped_vmem) instead of its default."""
    tk = t if tk is None else tk
    rows = max(t, tk)
    block_q = min(block_q, t)
    block_k = min(block_k, tk)
    while t % block_q:
        block_q //= 2
    while tk % block_k:
        block_k //= 2

    def over(bq, bk, free=0):
        return vmem_estimate(rows, d, bq, bk, itemsize, dv) - free > \
            VMEM_BUDGET_BYTES

    free = 0
    if over(min(block_q, 128), min(block_k, 128)):
        free = resident_row_bytes(rows, d, itemsize, dv)
    while over(block_q, block_k, free) and max(block_q, block_k) > 128:
        if block_k >= block_q and block_k > 128:
            block_k //= 2
        else:
            block_q //= 2
    return block_q, block_k


def scoped_vmem(t, d, block_q, block_k, itemsize, dv=None):
    """The ``vmem_limit_bytes`` a forward, dq or dkv instance asks
    Mosaic for: None (the compiler's default, SCOPED_VMEM_BYTES) while
    the full rows it keeps resident (K and V, or Q and dO: t x (d +
    dv)), which the pipeline holds in two buffers, take less than the
    whole budget the block clamp works to; every call did before an
    8k sequence at 192 + 128.  From there on the default leaves the
    tiles under 6 MB (the cell's bfloat16 dkv call asked for 16.56 of
    16 MB inside its train step), and the call asks for twice its
    estimate and 16 MB for the tiles' temporaries (a float32 dkv call
    with 512 x 1024 tiles takes 42.4 MB: its full-precision products
    split every operand), of the 128 MB a v5e core has.

    Rows narrower than the 128 lanes of a VMEM tile lie in 128 each:
    they are counted so, and ask from where their two buffers take
    half of Mosaic's default (an 8k sequence at width 64: 4 MB by its
    numbers and 8 MB where it lies; inside LFM2's train step the
    bfloat16 dkv call of 32 query heads over 8 K/V heads was refused
    at 16.07 of 16 MB, its float32 twin alone at 18).  Every narrower
    call that ran before (width 64 up to 2048 keys) stays at the
    default, as it was."""
    dv = d if dv is None else dv
    if min(d, dv) < 128:
        rows = resident_row_bytes(t, max(d, 128), itemsize, max(dv, 128))
        if 2 * rows < SCOPED_VMEM_BYTES // 2:
            return None
    elif 2 * resident_row_bytes(t, d, itemsize, dv) < VMEM_BUDGET_BYTES:
        return None
    estimate = vmem_estimate(t, d, block_q, block_k, itemsize, dv)
    return min(2 * estimate + VMEM_HEADROOM_BYTES, VMEM_LIMIT_CAP_BYTES)


def _lanes(width):
    """The lanes a row ``width`` elements wide lies in: VMEM tiles are
    128 lanes wide, so 64 lies in 128 and 192 in 256."""
    return -(-width // 128) * 128


def one_pass_backward_vmem(t, tk, d, dv, block_q, block_k, itemsize,
                           group=1, q_vectors=2, k_vectors=0, heads=1):
    """Bytes ONE instance of the one-pass flash backward
    (flash_attention._flash_bwd_fused_kernel, grid over heads) holds
    in VMEM, counted as Mosaic lays them out:

    - the q, dO (``t`` long) and k, v (``tk`` long) row inputs and the
      dq, dk, dv row outputs, each in the pipeline's TWO buffers, a
      row as wide as the lanes it lies in (_lanes);
    - the f32 dq scratch and, where ``group`` query heads share a K/V
      head, the f32 dk and dv scratch (one buffer each);
    - the [1, n] float32 vectors, 8 sublanes each, two buffers:
      ``q_vectors`` of length t (lse, delta, and the lse cotangent
      where there is one), ``k_vectors`` of length tk (the key bias
      and its gradient);
    - two chains' [block_q, block_k] score tiles (s -> p and dO v^T ->
      ds), each as score_tile_bytes() counts one, and twice that for
      float32 operands, whose full-precision products split every
      operand into bfloat16 parts that lie beside it
      (room_for_second_tile counts them so too).

    An instance that holds a PAIR of 64-wide heads (``heads`` = 2; its
    rows are the pair's 128 lanes, so ``d`` = ``dv`` = 128 and the
    rows cost what one head's cost in the lanes they lay in) runs the
    two chains once a head: four tiles.  Its [2, n] vectors lie in the
    8 sublanes a [1, n] one takes.

    The gate between the one-pass and the two-pass backward, and what
    the one-pass call asks of Mosaic, both read this count
    (one_pass_backward_limit); tests/test_chip_compile.py compiles the
    cells' shapes against it."""
    wide = _lanes(d) + _lanes(dv)
    rows_in = (t + tk) * wide * itemsize
    rows_out = (t * _lanes(d) + tk * wide) * itemsize
    vectors = 8 * 4 * (q_vectors * t + k_vectors * tk)
    scratch = t * _lanes(d) * 4 + (tk * wide * 4 if group > 1 else 0)
    tiles = 2 * heads * score_tile_bytes(block_q, block_k) * \
        max(itemsize // 2, 1)
    return 2 * (rows_in + rows_out + vectors) + scratch + tiles


def one_pass_backward_limit(count):
    """(admitted, ``vmem_limit_bytes``) of a one-pass backward call
    whose instance holds ``count`` bytes (one_pass_backward_vmem).
    Under Mosaic's default the call asks for nothing (None) and lowers
    as it always has; over it, for the count and the headroom
    scoped_vmem adds; and where that passes the cap every call here
    keeps to, the one-pass kernel is not admitted and the two-pass
    kernels (whose instances hold K and V, or Q and dO, not all four
    and three outputs) run."""
    if count <= SCOPED_VMEM_BYTES:
        return True, None
    limit = count + VMEM_HEADROOM_BYTES
    return limit <= VMEM_LIMIT_CAP_BYTES, limit


def record_dispatch(kernel, fused, reason, interpret=False):
    """Account one dispatch decision: counters + last-decision entry
    (dispatch()'s bookkeeping half)."""
    try:
        from ...fluid import monitor
        monitor.add('pallas/%s/dispatch_%s'
                    % (kernel, 'fused' if fused else 'dense'), 1)
        if not fused:
            monitor.add('pallas/%s/fallback/%s' % (kernel, reason), 1)
    except Exception:
        pass
    _LAST[kernel] = {'path': 'fused' if fused else 'dense',
                     'reason': reason, 'interpret': bool(interpret)}


def decide(enabled, checks=(), force=None, auto_partitioned=False):
    """dispatch()'s decision, recording nothing: ``(use_fused, reason,
    interpret)``.  A caller that can wrap its kernel in a shard_map
    asks it, without ``auto_partitioned``, whether every other gate
    passes before it opens one."""
    if not enabled:
        return False, 'flag_off', False
    for reason, ok in checks:
        if reason not in _FALLBACK_REASONS:
            raise ValueError('unknown fallback reason %r' % (reason,))
        if not ok:
            return False, reason, False
    if auto_partitioned:
        return False, 'auto_partitioned', False
    if on_tpu():
        return True, 'tpu', False
    if force if force is not None else force_fused():
        return True, 'forced_interpret', True
    return False, 'off_tpu', False


def dispatch(kernel, enabled, checks=(), force=None,
             auto_partitioned=False):
    """The auto-dispatch gate.  ``checks`` is a sequence of
    ``(reason, ok)`` pairs evaluated in order (reasons from
    _FALLBACK_REASONS: 'below_floor', 'vmem_over_budget', 'dtype',
    'layout'); the first failing gate names the fallback.  Returns
    ``(use_fused, interpret)`` — interpret=True means the fused body
    runs under the Pallas interpreter (off-TPU force mode).

    Gate order: flag first (an off flag falls back even on TPU), then
    the kernel's own checks, then ``auto_partitioned``, then the
    platform.  ``force`` (default FLAGS_pallas_force) only overrides
    the PLATFORM gate — a kernel whose shape/dtype gates fail stays
    dense even under force, so forced parity runs still exercise the
    real gates.

    ``auto_partitioned`` is the caller's word that this call sits, as
    it stands, in ONE program XLA will partition over a multi-device
    mesh — the GSPMD runner of with_data_parallel / with_mesh — and
    that the caller wraps nothing.  XLA cannot partition a Mosaic
    kernel ("Mosaic kernels cannot be automatically partitioned.
    Please wrap the call in a shard_map"), so the dense lowering —
    which it can — is the only one that compiles there.  Code inside a
    shard_map is per-device and passes nothing: the flash op's
    lowerings take that road (flash_attention.mesh_flash_attention
    splits the batch over the runner's batch axes and calls the
    kernels on each device's share; where it cannot, it answers dense
    under 'batch_not_split')."""
    fused, reason, interpret = decide(enabled, checks, force,
                                      auto_partitioned)
    record_dispatch(kernel, fused, reason, interpret)
    return fused, interpret


def report():
    """/statusz section: per-kernel registration + last decision +
    dispatch/fallback counter values (and, for flash attention, the
    backward lowerings by kind, the fused lowerings by the layout
    their kernels address and the largest ``vmem_limit_bytes`` a call
    asked for), and under 'dropout' the dropout
    op's draws from the kernels' counter hash (ops/keep_hash.py):
    lowerings counted and the elements the last traced program draws
    a step, under 'hyper_connections' the mHC ops' lowerings, streams,
    Sinkhorn iterations and H_res's last distance from the doubly
    stochastic matrices, and under 'mtp' the prediction module's last
    loss and its share of the training loss
    (ops/hyper_connection_ops.py, models/xing4.py).  Empty dict when
    nothing has dispatched or drawn yet (health.py hides the
    section)."""
    try:
        from ...fluid import monitor
        counter = monitor.counter_value
        gauge = monitor.gauge_value
    except Exception:
        def counter(name):
            return 0
        gauge = counter
    out = {}
    for name, info in sorted(KERNELS.items()):
        fused = counter('pallas/%s/dispatch_fused' % name) or 0
        dense = counter('pallas/%s/dispatch_dense' % name) or 0
        last = _LAST.get(name)
        if not fused and not dense and last is None:
            continue
        ent = {'dense_fallback': info['dense_fallback'],
               'has_vjp': info['has_vjp'],
               'dispatch_fused': fused, 'dispatch_dense': dense}
        sharded = counter('pallas/%s/dispatch_sharded' % name) or 0
        if sharded:
            # of the fused ones: lowered inside a shard_map over the
            # GSPMD runner's batch axes
            ent['dispatch_sharded'] = sharded
        # of the fused ones, those a second set of kernels took (flash
        # attention: a handful of keys, small_keys.py); which backward
        # a kernel with two of them lowered (flash
        # attention: one pass over a head's rows, or dq then dkv), which
        # layout its kernels address (flash attention: a pair of
        # 64-wide heads in the op's own [B, T, H x 64], or one head of
        # a transposed [B x H, T, D] copy), and the most scoped VMEM
        # any of its calls asked Mosaic for
        for key in ('dispatch_small_keys', 'backward_one_pass',
                    'backward_two_pass', 'layout_paired',
                    'layout_transposed'):
            n = counter('pallas/%s/%s' % (name, key)) or 0
            if n:
                ent[key] = n
        asked = gauge('pallas/%s/vmem_asked_max' % name) or 0
        if asked:
            ent['vmem_asked_max'] = int(asked)
        if info.get('op_types'):
            ent['op_types'] = list(info['op_types'])
        if last:
            ent['last'] = dict(last)
        fb = {}
        for reason in _FALLBACK_REASONS:
            n = counter('pallas/%s/fallback/%s' % (name, reason)) or 0
            if n:
                fb[reason] = n
        if fb:
            ent['fallbacks'] = fb
        out[name] = ent
    rep = {'kernels': out} if out else {}
    draws = counter('dropout/counter_draws') or 0
    if draws:
        rep['dropout'] = {'counter_draws': draws,
                          'elements': gauge('dropout/elements') or 0}
    calls = counter('mhc/calls') or 0
    if calls:
        rep['hyper_connections'] = {
            'calls': calls, 'streams': gauge('mhc/streams') or 0,
            'sinkhorn_iters': gauge('mhc/sinkhorn_iters') or 0,
            'stochastic_err': gauge('mhc/stochastic_err') or 0}
        if gauge('mtp/loss'):
            rep['mtp'] = {'loss': gauge('mtp/loss'),
                          'loss_share': gauge('mtp/loss_share') or 0}
    return rep
