"""Core runtime objects: places, Scope, dtype conversion, LoDTensor.

TPU-native re-design of the reference framework core:
  - Place        (reference: paddle/fluid/platform/place.h:26-98)
  - Scope        (reference: paddle/fluid/framework/scope.h:46-99)
  - LoDTensor    (reference: paddle/fluid/framework/lod_tensor.h:52-219)
  - SelectedRows (reference: paddle/fluid/framework/selected_rows.h:32-44)

Unlike the reference (type-erased C++ holders + buddy allocator), values here
are jax.Array / numpy arrays; device memory management is XLA's job.  The
Scope keeps the reference's name->Variable contract with parent-chain lookup
so executors, save/load and the fleet API work unchanged.
"""

import weakref

import numpy as np
import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Places
# ---------------------------------------------------------------------------


class Place(object):
    """Device tag. Reference: platform/place.h boost::variant of places."""

    def __init__(self, device_id=0):
        self.device_id = int(device_id)

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self.device_id)

    def jax_device(self):
        raise NotImplementedError


class CPUPlace(Place):
    def __init__(self):
        super(CPUPlace, self).__init__(0)

    def jax_device(self):
        try:
            return jax.local_devices(backend="cpu")[0]
        except RuntimeError:
            return jax.local_devices()[0]


class XLAPlace(Place):
    """The accelerator place (TPU when available). Replaces CUDAPlace
    (reference: platform/place.h:79) as the one-line user-visible swap:
    fluid.CUDAPlace(0) -> fluid.XLAPlace(0)."""

    def jax_device(self):
        # PROCESS-LOCAL device index, matching the reference semantics
        # where CUDAPlace(i) is trainer-local GPU i (each NCCL2-mode
        # trainer process owns its own device numbering).  On a
        # single-process runtime local == global.  An index past the
        # local devices is an error: wrapping it would put a program
        # that asked for a second chip on the first.
        devs = jax.local_devices()
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                'XLAPlace(%d): this process has %d local %s device(s)'
                % (self.device_id, len(devs), devs[0].platform))
        return devs[self.device_id]


# Compatibility alias: existing fluid scripts use CUDAPlace.
CUDAPlace = XLAPlace


class CUDAPinnedPlace(CPUPlace):
    pass


def is_compiled_with_cuda():
    return False


def is_compiled_with_xla():
    return True


# ---------------------------------------------------------------------------
# dtype conversion
# ---------------------------------------------------------------------------

# Reference dtype enum: framework/framework.proto:104 (VarType.Type)
_DTYPE_MAP = {
    "bool": np.bool_,
    "int8": np.int8,
    "int16": np.int16,
    "int32": np.int32,
    "int64": np.int64,
    "uint8": np.uint8,
    "float16": np.float16,
    "bfloat16": jnp.bfloat16,
    "float32": np.float32,
    "float64": np.float64,
}

# Numeric values of VarType.Type for proto-level compat
# (framework/framework.proto:104-131).
VARTYPE_TO_NAME = {
    0: "bool", 1: "int16", 2: "int32", 3: "int64", 4: "float16",
    5: "float32", 6: "float64", 20: "uint8", 21: "int8", 22: "bfloat16",
}
NAME_TO_VARTYPE = {v: k for k, v in VARTYPE_TO_NAME.items()}


def convert_dtype(dtype):
    """Accept str ('float32'), numpy dtype, jnp dtype, or VarType int.

    int64/uint64/float64 map to their 32-bit widths when jax runs with
    x64 disabled (the default): jax would truncate them anyway, this
    just does it without emitting a warning per op."""
    if dtype is None:
        return np.dtype(np.float32)
    if isinstance(dtype, int):
        dtype = VARTYPE_TO_NAME[dtype]
    if isinstance(dtype, str):
        if dtype == "bfloat16":
            return jnp.dtype(jnp.bfloat16)
        dt = np.dtype(_DTYPE_MAP[dtype])
    else:
        try:
            dt = np.dtype(dtype)
        except TypeError:
            return jnp.dtype(dtype)
    if dt.itemsize == 8 and dt.kind in 'iuf' and \
            not jax.config.jax_enable_x64:
        dt = np.dtype({'i': np.int32, 'u': np.uint32,
                       'f': np.float32}[dt.kind])
    return dt


def dtype_name(dtype):
    return convert_dtype(dtype).name


# ---------------------------------------------------------------------------
# LoDTensor / SelectedRows
# ---------------------------------------------------------------------------


class LoDTensor(object):
    """Dense tensor + level-of-detail offsets for variable-length batches.

    Reference: framework/lod_tensor.h:52 (LoD = vector<Vector<size_t>>).
    On TPU the data itself is padded/bucketed before compilation; the LoD
    rides along on the host and drives mask construction in sequence ops.
    """

    def __init__(self, data, lod=None):
        self.data = data
        self.lod = [list(level) for level in (lod or [])]

    def set_lod(self, lod):
        self.lod = [list(level) for level in lod]

    def recursive_sequence_lengths(self):
        out = []
        for level in self.lod:
            out.append([level[i + 1] - level[i] for i in range(len(level) - 1)])
        return out

    def __array__(self, dtype=None):
        arr = np.asarray(self.data)
        return arr.astype(dtype) if dtype is not None else arr

    @property
    def shape(self):
        return tuple(self.data.shape)

    @property
    def dtype(self):
        return self.data.dtype


class SelectedRows(object):
    """Sparse row-set: int row ids + dense rows value tensor.

    Reference: framework/selected_rows.h:32-44.  Used for sparse gradients
    of embedding lookups; on TPU the optimizer ops apply it as a
    segment-sum scatter-update instead of a per-row hash map.
    """

    def __init__(self, rows, value, height):
        self.rows = rows          # int array [n]
        self.value = value        # [n, dim...]
        self.height = int(height)  # full first-dim size

    def to_dense(self):
        out = jnp.zeros((self.height,) + tuple(self.value.shape[1:]),
                        self.value.dtype)
        return out.at[self.rows].add(self.value)


# ---------------------------------------------------------------------------
# Scope
# ---------------------------------------------------------------------------


_ERASED = object()  # pop sentinel for Scope.erase


class Scope(object):
    """name -> value map with parent-chain lookup and child scopes.

    Reference: framework/scope.h:46 (Var/FindVar/kids).  Values are
    jax.Array, numpy arrays, LoDTensor or SelectedRows.

    The scope is VERSIONED for the executor's steady-state fast path:
    `_struct_version` counts STRUCTURAL mutations only — a name
    appearing in or leaving this scope's own dict — and overwriting an
    existing name (the per-step device write-back of segment outputs)
    does not bump it.  Segment argument binders cache which scope dict
    owns each variable name and revalidate against `_chain_token()`, so
    the per-step state/data bind is one dict read per name instead of a
    parent-chain walk: device-resident values (jax.Array segment
    outputs) flow between consecutive segments and steps by pointer.
    """

    def __init__(self, parent=None):
        self._vars = {}
        self.parent = parent
        self.kids = []
        self._struct_version = 0

    def new_scope(self):
        kid = Scope(self)
        self.kids.append(kid)
        return kid

    def var(self, name):
        if name not in self._vars:
            self._vars[name] = None
            self._struct_version += 1
        return name

    def set_var(self, name, value):
        if name not in self._vars:
            self._struct_version += 1
        self._vars[name] = value

    def find_var(self, name):
        s = self
        while s is not None:
            if name in s._vars:
                return s._vars[name]
            s = s.parent
        return None

    def has_var(self, name):
        s = self
        while s is not None:
            if name in s._vars and s._vars[name] is not None:
                return True
            s = s.parent
        return False

    def erase(self, name):
        if self._vars.pop(name, _ERASED) is not _ERASED:
            self._struct_version += 1

    def local_var_names(self):
        return list(self._vars.keys())

    def drop_kids(self):
        self.kids = []

    # ---- fast-path binding surface (executor._SegmentBinder) --------
    def _owner_vars(self, name):
        """The `_vars` dict along the parent chain that holds `name`,
        or None.  Binders cache this dict so steady-state reads skip
        the chain walk; validity is guarded by `_chain_token()`."""
        s = self
        while s is not None:
            if name in s._vars:
                return s._vars
            s = s.parent
        return None

    def _chain_token(self):
        """Structural version summed over the parent chain.  A cached
        owner-dict resolution is valid while this token is unchanged:
        value overwrites keep the token, so per-step output write-back
        never invalidates a binder."""
        t = 0
        s = self
        while s is not None:
            t += s._struct_version
            s = s.parent
        return t


_global_scope = Scope()


def global_scope():
    return _global_scope


class _ScopeGuard(object):
    def __init__(self, scope):
        self.scope = scope

    def __enter__(self):
        global _global_scope
        self._old = _global_scope
        _global_scope = self.scope

    def __exit__(self, *a):
        global _global_scope
        _global_scope = self._old


def scope_guard(scope):
    return _ScopeGuard(scope)


def as_array(value):
    """Pull the dense array out of whatever the scope holds."""
    if isinstance(value, LoDTensor):
        return value.data
    if isinstance(value, SelectedRows):
        return value.to_dense()
    return value


# ---------------------------------------------------------------------------
# Device-buffer ownership registry
# ---------------------------------------------------------------------------
# Arrays the RUNTIME created and never exposed to the caller (the
# executor's per-step feed staging) are safe to hand to a jitted
# segment as donated state: no caller holds them, so invalidating the
# buffer is invisible.  Reader-staged batches do NOT qualify — the
# batch dict is returned to user code.  A
# jax.Array the CALLER fed must never be donated — the executor copies
# it instead.  This registry turns that per-step defensive copy into a
# once-per-buffer membership check: jax.Array identity keyed by id()
# with a weakref finalizer, so entries die with the buffer and a
# recycled address can never alias a stale claim.

_owned_buffers = {}


def mark_owned(arr):
    """Record `arr` as runtime-created (donation-safe).  No-op for
    values that don't support weakrefs (numpy scalars etc.)."""
    i = id(arr)
    try:
        _owned_buffers[i] = weakref.ref(
            arr, lambda _r, _i=i: _owned_buffers.pop(_i, None))
    except TypeError:
        pass
    return arr


def is_owned(arr):
    """True iff `arr` is the SAME object previously mark_owned()ed."""
    r = _owned_buffers.get(id(arr))
    return r is not None and r() is arr


def disown(arr):
    """Withdraw a mark_owned() claim: `arr` has grown a second
    consumer (another segment, the scope), so donating it by pointer
    would invalidate that consumer — binders fall back to the copy."""
    _owned_buffers.pop(id(arr), None)
    return arr
