"""TPU column vectors: static-shape, validity-masked JAX arrays.

Role parallel to the reference's `GpuColumnVector.java:39` (a Spark
ColumnVector wrapping a cuDF device column).  The TPU twist: XLA compiles
per shape, so every vector is padded to a *bucketed capacity* (powers of two)
and carries an explicit validity mask.  A batch's logical row count lives on
the host (`ColumnarBatch.num_rows`); inside jitted kernels the row mask is
derived from an iota < num_rows operand so the same executable serves every
batch in the bucket.

Strings (reference: cuDF string columns) are a uint8[capacity, char_cap]
byte tensor plus int32 lengths — fixed-width so string kernels vectorize on
the VPU (see exprs/strings.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from spark_rapids_tpu import types as T

# ---------------------------------------------------------------------------
# capacity bucketing — the compile-cache key discipline (SURVEY.md §7 hard
# part (a)): batches are padded to the next bucket so XLA executables are
# reused across batches.
MIN_CAPACITY = 32
MIN_CHAR_CAP = 8


def bucket_capacity(n: int, minimum: int = MIN_CAPACITY) -> int:
    cap = minimum
    while cap < n:
        cap *= 2
    return cap


def bucket_char_cap(n: int) -> int:
    return bucket_capacity(max(n, 1), MIN_CHAR_CAP)


def _f32_shadow(x_f64: np.ndarray) -> np.ndarray:
    """FLOAT64 -> f32 narrow shadow with EXPLICIT overflow semantics
    (a bare astype overflows finite values to ±inf with a silent
    RuntimeWarning — exactly where a parity bug would hide).
    Invariants consumers rely on:
      - monotone: x <= y  =>  shadow(x) <= shadow(y)  (top-k pruning)
      - finiteness preserved: finite f64 -> finite f32 (clamped to
        ±f32max past the f32 range), ±inf -> ±inf, NaN -> NaN
      - sign preserved (incl. -0.0)."""
    with np.errstate(over="ignore"):
        n32 = x_f64.astype(np.float32)
    over = np.isinf(n32)
    if over.any():          # rare: only then is the f64 column read again
        over &= np.isfinite(x_f64)
        fmax = np.finfo(np.float32).max
        n32 = np.where(over, np.copysign(fmax, x_f64).astype(np.float32),
                       n32)
    return n32


def _pad_to(arr: np.ndarray, capacity: int, axis: int = 0) -> np.ndarray:
    n = arr.shape[axis]
    if n == capacity:
        return arr
    pad = [(0, 0)] * arr.ndim
    pad[axis] = (0, capacity - n)
    return np.pad(arr, pad)


def host_validity(values, validity: Optional[np.ndarray]) -> np.ndarray:
    """A column's bool validity on the host, unpadded: the caller's
    mask, else an Arrow array's bitmap, else None-ness of object
    values, else all True (NaN is a value, not null: Spark)."""
    if validity is None:
        if isinstance(values, pa.Array):
            if not values.null_count:
                return np.ones(len(values), bool)
            return values.is_valid().to_numpy(zero_copy_only=False)
        if values.dtype == object:
            return np.array([v is not None for v in values], bool)
        return np.ones(len(values), bool)
    return np.asarray(validity, bool)


def host_storage(values: np.ndarray, dtype: T.DataType) -> np.ndarray:
    """A fixed-width column's values in its storage dtype on the host,
    unpadded: None -> 0, datetimes -> int64 micros; a view where the
    dtype already matches."""
    storage = dtype.storage_dtype
    if values.dtype == object:
        return np.array([v if v is not None else 0 for v in values],
                        dtype=storage)
    if values.dtype.kind == "M":
        return values.astype("datetime64[us]").astype(np.int64)
    return np.asarray(values).astype(storage, copy=False)


def host_narrow(safe: np.ndarray, dtype: T.DataType
                ) -> Optional[np.ndarray]:
    """The 32-bit shadow of a host storage array (see
    `ColumnVector.narrow`), or None: int32 for an INT64 array whose
    every value fits, `_f32_shadow` for FLOAT64."""
    if dtype.id == T.TypeId.INT64 and len(safe):
        lo, hi = safe.min(), safe.max()
        if np.iinfo(np.int32).min <= lo and hi <= np.iinfo(np.int32).max:
            return safe.astype(np.int32)
    elif dtype.id == T.TypeId.FLOAT64:
        return _f32_shadow(safe)
    return None


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ColumnVector:
    """One column: `data` padded to capacity, `validity` True where non-null.

    For STRING columns `data` is uint8[capacity, char_cap] and `lengths`
    int32[capacity]; otherwise `lengths` is None.

    `narrow` is an optional 32-BIT SHADOW of `data`: 64-bit elementwise
    ops are ~50-100x slower than 32-bit on TPU (no native 64-bit; XLA
    emulates), so sources upload an i32 copy of INT64 columns whose
    values fit int32 (EXACT — verified host-side) and an f32 copy of
    FLOAT64 columns (LOSSY — only used by paths that already carry
    variableFloatAgg-class tolerance).  Kernels check for it at trace
    time (it is part of the batch signature).

    How the arrays reach the device: `from_numpy` pads on the host and
    sends each array of one batch by itself.  A plan's in-memory source
    (`plan/overrides._conv_source`) builds the same vectors through
    `ColumnarBatch.chunks_from_numpy`: the host halves here
    (`host_storage`, `host_validity`, `host_narrow`; `host_strings` and
    `encode_strings` for a STRING column) run once a column of a
    partition, the full chunks go to the device whole and are cut
    there.  The INT64 shadow is then decided once for the partition's
    run of chunks, not chunk by chunk.
    """
    dtype: T.DataType
    data: jnp.ndarray
    validity: jnp.ndarray
    lengths: Optional[jnp.ndarray] = None
    narrow: Optional[jnp.ndarray] = None

    # -- pytree protocol so vectors flow through jit/shard_map --------------
    def tree_flatten(self):
        children = (self.data, self.validity, self.lengths, self.narrow)
        return children, self.dtype

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, validity, lengths, narrow = children
        return cls(aux, data, validity, lengths, narrow)

    # -----------------------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    @property
    def device_arrays(self) -> int:
        """How many device arrays the column is made of."""
        return 2 + (self.lengths is not None) + (self.narrow is not None)

    @property
    def char_cap(self) -> int:
        assert self.dtype.is_string
        return self.data.shape[1]

    def has_nulls_upto(self, num_rows: int) -> bool:
        v = np.asarray(self.validity[:num_rows])
        return not bool(v.all())

    # -- host <-> device ----------------------------------------------------
    @staticmethod
    def from_numpy(values: np.ndarray, dtype: Optional[T.DataType] = None,
                   validity: Optional[np.ndarray] = None,
                   capacity: Optional[int] = None) -> "ColumnVector":
        if dtype is None:
            dtype = T.STRING if isinstance(values, pa.Array) \
                else T.from_numpy_dtype(values.dtype)
        n = len(values)
        cap = capacity or bucket_capacity(n)
        validity = host_validity(values, validity)

        if dtype.is_string:
            strings = host_strings(values)
            if strings is None:     # Arrow refused the values: one by one
                return _strings_from_host(values, _pad_to(validity, cap),
                                          cap)
            flat, (cc,), validity, lengths = encode_strings(
                *string_buffers(strings), validity, cap)
            return ColumnVector(dtype, jnp.asarray(flat.reshape(cap, cc)),
                                jnp.asarray(validity), jnp.asarray(lengths))

        validity = _pad_to(validity, cap)
        safe = _pad_to(host_storage(values, dtype), cap)
        narrow = host_narrow(safe, dtype)
        return ColumnVector(dtype, jnp.asarray(safe), jnp.asarray(validity),
                            None, None if narrow is None
                            else jnp.asarray(narrow))

    @staticmethod
    def from_scalar(value: Any, dtype: T.DataType, capacity: int,
                    num_rows: int) -> "ColumnVector":
        """Broadcast a scalar to a column (partition values, literals)."""
        if value is None:
            validity = jnp.zeros(capacity, bool)
            if dtype.is_string:
                data = jnp.zeros((capacity, MIN_CHAR_CAP), jnp.uint8)
                return ColumnVector(dtype, data, validity,
                                    jnp.zeros(capacity, jnp.int32))
            return ColumnVector(
                dtype, jnp.zeros(capacity, dtype.storage_dtype), validity)
        validity = jnp.arange(capacity) < num_rows
        if dtype.is_string:
            raw = np.frombuffer(str(value).encode("utf-8"), np.uint8)
            cc = bucket_char_cap(len(raw))
            data = np.zeros((capacity, cc), np.uint8)
            data[:, : len(raw)] = raw
            lengths = jnp.where(validity, len(raw), 0).astype(jnp.int32)
            return ColumnVector(dtype, jnp.asarray(data), validity, lengths)
        data = jnp.full(capacity, value, dtype.storage_dtype)
        return ColumnVector(dtype, data, validity)

    def to_numpy(self, num_rows: int) -> tuple[np.ndarray, np.ndarray]:
        """Return (values, validity) trimmed to num_rows; strings decode to
        an object array of python str (None for nulls)."""
        validity = np.asarray(self.validity)[:num_rows]
        if self.dtype.is_string:
            raw = np.asarray(self.data)[:num_rows]
            lens = np.asarray(self.lengths)[:num_rows]
            out = np.empty(num_rows, object)
            for i in range(num_rows):
                out[i] = (raw[i, : lens[i]].tobytes().decode("utf-8", "replace")
                          if validity[i] else None)
            return out, validity
        vals = np.asarray(self.data)[:num_rows]
        if self.dtype.id == T.TypeId.TIMESTAMP_US:
            pass  # keep int64 micros; callers convert for display
        return vals, validity

    def to_pylist(self, num_rows: int) -> list:
        vals, validity = self.to_numpy(num_rows)
        if self.dtype.is_string:
            return list(vals)
        return [vals[i].item() if validity[i] else None
                for i in range(num_rows)]

    # -- structural ops (host orchestration; device work stays in kernels) --
    def with_capacity(self, capacity: int) -> "ColumnVector":
        if capacity == self.capacity:
            return self
        if capacity < self.capacity:
            data = self.data[:capacity]
            validity = self.validity[:capacity]
            lengths = None if self.lengths is None else self.lengths[:capacity]
            narrow = (None if self.narrow is None
                      else self.narrow[:capacity])
        else:
            extra = capacity - self.capacity
            data = jnp.concatenate(
                [self.data, jnp.zeros((extra,) + self.data.shape[1:],
                                      self.data.dtype)])
            validity = jnp.concatenate([self.validity,
                                        jnp.zeros(extra, bool)])
            lengths = (None if self.lengths is None else
                       jnp.concatenate([self.lengths,
                                        jnp.zeros(extra, jnp.int32)]))
            narrow = (None if self.narrow is None else jnp.concatenate(
                [self.narrow, jnp.zeros(extra, self.narrow.dtype)]))
        return ColumnVector(self.dtype, data, validity, lengths, narrow)

    def gather(self, indices: jnp.ndarray,
               index_valid: Optional[jnp.ndarray] = None) -> "ColumnVector":
        """Take rows by index (cuDF gather analog). indices beyond num_rows
        must point at padded/zero rows; index_valid marks rows kept."""
        data = jnp.take(self.data, indices, axis=0, mode="clip")
        validity = jnp.take(self.validity, indices, mode="clip")
        if index_valid is not None:
            validity = validity & index_valid
        lengths = (None if self.lengths is None
                   else jnp.take(self.lengths, indices, mode="clip"))
        narrow = (None if self.narrow is None
                  else jnp.take(self.narrow, indices, mode="clip"))
        return ColumnVector(self.dtype, data, validity, lengths, narrow)


#: how many column validities fit one packed-i32 bitmask (callers batch
#: all columns' validity resolution into ONE random-access stream)
VMASK_BITS = 30


def validity_bit_assignment(columns) -> dict:
    """{ordinal: bit} for the first VMASK_BITS NON-STRING columns
    (strings resolve validity inside their own gather, so giving them a
    bit would waste mask capacity).  Pure dtype metadata — safe to call
    from either side of a producer/consumer kernel pair; both sides get
    the SAME assignment by construction."""
    bits: dict = {}
    for ci, c in enumerate(columns):
        if c.dtype.is_string:
            continue
        if len(bits) >= VMASK_BITS:
            break
        bits[ci] = len(bits)
    return bits


def pack_validity_bits(columns):
    """`validity_bit_assignment` plus the packed i32 mask itself, one
    bit per column per row.  Returns ({ordinal: bit}, mask-or-None)."""
    bits = validity_bit_assignment(columns)
    if not bits:
        return bits, None
    packed = jnp.zeros(columns[0].validity.shape[0], jnp.int32)
    for ci, bit in bits.items():
        packed = packed | (columns[ci].validity.astype(jnp.int32) << bit)
    return bits, packed


def gather_columns_grouped(columns, order, valid, packed_bits=None):
    """Reorder EVERY column by `order` with the fewest random-access
    streams.  A gather's cost on this chip is per random ROW ACCESS
    (~70ns), not per byte, so all 4-byte value streams (i32 data,
    narrow shadows, bitcast f32, upcast i8/i16/bool, the packed
    validity word) stack into ONE [cap, k] gather, and all f64 streams
    into another — a wide numeric batch reorders in ~2 random streams
    instead of one per column.  Strings keep their own char-tensor
    gathers.  Returns the reordered column list; `valid` marks live
    output rows."""
    from jax import lax
    bits, packed = (pack_validity_bits(columns) if packed_bits is None
                    else packed_bits)
    g32, g64f, g64i, plans = [], [], [], []
    if packed is not None:
        vm_slot = len(g32)
        g32.append(packed)
    for ci, c in enumerate(columns):
        if c.dtype.is_string:
            plans.append(("string", None, None, None))
            continue
        dt = c.data.dtype
        if c.narrow is not None and c.dtype.id in (T.TypeId.INT64,
                                                   T.TypeId.TIMESTAMP_US):
            plans.append(("narrow64", len(g32), ci, None))
            g32.append(c.narrow)
        elif dt == jnp.int32:
            plans.append(("i32", len(g32), ci, None))
            g32.append(c.data)
        elif dt == jnp.float32:
            plans.append(("f32", len(g32), ci, None))
            g32.append(lax.bitcast_convert_type(c.data, jnp.int32))
        elif dt in (jnp.dtype(jnp.bool_), jnp.dtype(jnp.int8),
                    jnp.dtype(jnp.int16)):
            plans.append((str(dt), len(g32), ci, None))
            g32.append(c.data.astype(jnp.int32))
        elif dt == jnp.float64:
            nslot = None
            if c.narrow is not None:  # lossy f32 shadow rides the i32 bus
                nslot = len(g32)
                g32.append(lax.bitcast_convert_type(
                    c.narrow.astype(jnp.float32), jnp.int32))
            plans.append(("f64", len(g64f), ci, nslot))
            g64f.append(c.data)
        else:  # int64/timestamp without a narrow shadow
            plans.append(("i64", len(g64i), ci, None))
            g64i.append(c.data)

    def taker(group):
        if not group:
            return lambda i: None
        if len(group) == 1:
            g = jnp.take(group[0], order, mode="clip")
            return lambda i: g
        stacked = jnp.take(jnp.stack(group, axis=1), order, axis=0,
                           mode="clip")
        return lambda i: stacked[:, i]

    t32, t64f, t64i = taker(g32), taker(g64f), taker(g64i)
    vm = t32(vm_slot) if packed is not None else None
    out = []
    for (kind, slot, ci, nslot), c in zip(plans, columns):
        if kind == "string":
            out.append(c.gather(order, valid))
            continue
        if ci in bits:
            v = valid & (((vm >> bits[ci]) & 1) != 0)
        else:  # beyond the 32-bit mask: own validity stream
            v = valid & jnp.take(c.validity, order, mode="clip")
        if kind == "narrow64":
            nd = t32(slot)
            out.append(ColumnVector(c.dtype, nd.astype(c.data.dtype),
                                    v, None, nd))
        elif kind == "i32":
            out.append(ColumnVector(c.dtype, t32(slot), v))
        elif kind == "f32":
            out.append(ColumnVector(
                c.dtype, lax.bitcast_convert_type(t32(slot), jnp.float32),
                v))
        elif kind == "f64":
            narrow = (None if nslot is None else
                      lax.bitcast_convert_type(t32(nslot), jnp.float32))
            out.append(ColumnVector(c.dtype, t64f(slot), v, None, narrow))
        elif kind == "i64":
            out.append(ColumnVector(c.dtype, t64i(slot), v))
        else:  # bool/int8/int16 round-trip through the i32 bus exactly
            out.append(ColumnVector(c.dtype,
                                    t32(slot).astype(c.data.dtype), v))
    return out


def gather_narrowest(c: ColumnVector, indices: jnp.ndarray,
                     valid: jnp.ndarray) -> ColumnVector:
    """Gather a non-string column's value streams with a PRE-RESOLVED
    validity (the caller batched validity into one packed-bitmask
    gather).  Random-access streams cost ~70ns/row on this chip, so:
    int64-with-narrow gathers ONLY the i32 shadow and widens exactly;
    everything else gathers data plus the narrow shadow if present."""
    from spark_rapids_tpu import types as T
    if c.narrow is not None and c.dtype.id in (T.TypeId.INT64,
                                               T.TypeId.TIMESTAMP_US):
        nd = jnp.take(c.narrow, indices, mode="clip")
        return ColumnVector(c.dtype, nd.astype(c.data.dtype), valid,
                            None, nd)
    data = jnp.take(c.data, indices, axis=0, mode="clip")
    narrow = (None if c.narrow is None
              else jnp.take(c.narrow, indices, mode="clip"))
    return ColumnVector(c.dtype, data, valid, None, narrow)


def host_strings(values, nan_is_null: bool = False
                 ) -> Optional[pa.LargeStringArray]:
    """A string column's host form, its Arrow buffers (offsets, UTF-8
    bytes, validity bitmap) and never a Python object per value: the
    `large_string` array a pandas `str` column or a scan already holds
    (no copy; a partition's slice keeps its offset), or one C pass over
    an object array of `str` / None (NaN too where the caller's mask
    says so: pandas).  None where Arrow refuses: a column holding
    numbers, other objects or bytes that are no UTF-8, which
    `_strings_from_host` stringifies value by value."""
    try:
        if isinstance(values, np.ndarray):
            if values.dtype.kind == "T":
                values = values.astype(object)
            if values.dtype.kind not in "OU":
                return None
            return pa.array(values, pa.large_string(),
                            from_pandas=nan_is_null)
        if not isinstance(values, (pa.Array, pa.ChunkedArray)):
            values = pa.array(values)       # pandas: `__arrow_array__`
        if isinstance(values, pa.ChunkedArray):
            values = values.combine_chunks()
        if pa.types.is_string(values.type):
            values = values.cast(pa.large_string())
        return values if pa.types.is_large_string(values.type) else None
    except (pa.ArrowInvalid, pa.ArrowTypeError, pa.ArrowNotImplementedError):
        return None


def string_buffers(strings: pa.LargeStringArray
                   ) -> tuple[np.ndarray, np.ndarray]:
    """(offsets int64[n + 1], bytes uint8[...]) of a `large_string`
    array as numpy views; value i is `bytes[offsets[i]:offsets[i + 1]]`
    (a slice's offsets do not start at 0)."""
    _, offsets, raw = strings.buffers()
    offsets = np.frombuffer(offsets, np.int64)[
        strings.offset:strings.offset + len(strings) + 1]
    return offsets, (np.frombuffer(raw, np.uint8) if raw is not None
                     else np.zeros(0, np.uint8))


def string_lengths(offsets: np.ndarray) -> np.ndarray:
    """Byte lengths int32[n] of the values between n + 1 offsets."""
    lens = np.empty(len(offsets) - 1, np.int32)
    np.subtract(offsets[1:], offsets[:-1], out=lens, casting="unsafe")
    return lens


def encode_strings(offsets: np.ndarray, raw: np.ndarray,
                   validity: np.ndarray, chunk_rows: int
                   ) -> tuple[np.ndarray, tuple, np.ndarray, np.ndarray]:
    """Arrow string buffers -> the padded host arrays string vectors are
    made of, with no Python call per value: the n values laid out in
    chunks of `chunk_rows` rows (one chunk at least, the last
    zero-padded), chunk i a row-major `u8[chunk_rows, char_caps[i]]`
    byte matrix at the bucket of ITS longest value, the matrices one
    after another in `flat`.  Returns (flat, char_caps, validity,
    lengths), the last two padded to the chunks' rows; a null's length
    is 0 and its row zeros, whatever bytes its offsets span.  One chunk
    (`chunk_rows` = the capacity) is one vector's arrays:
    `flat.reshape(capacity, char_caps[0])`."""
    n = len(offsets) - 1
    chunks = max(1, -(-n // chunk_rows))
    lens = string_lengths(offsets)
    if not validity.all():
        lens[~validity] = 0
    starts = np.arange(0, n, chunk_rows)
    caps = tuple(bucket_char_cap(int(m)) for m in
                 np.maximum.reduceat(lens, starts)) if n \
        else (MIN_CHAR_CAP,)
    flat = np.zeros(chunk_rows * sum(caps), np.uint8)
    longest = int(lens.max()) if n else 0
    if longest and int(lens.min()) == longest:
        # equal lengths (no null among them): the bytes ARE the matrix,
        # but for the padding of each row
        flat.reshape(-1, caps[0])[:n, :longest] = raw[
            offsets[0]:offsets[0] + n * longest].reshape(n, longest)
    elif longest:
        # ragged: byte k of the valid values' run lands at k + (its
        # row's place in `flat` - the bytes before its row): one repeat,
        # one scatter
        total = int(lens.sum(dtype=np.int64))
        before = np.cumsum(lens, dtype=np.int64) - lens
        row = np.arange(n, dtype=np.int64)
        if len(set(caps)) == 1:
            place = row * caps[0]
        else:
            cc = np.asarray(caps, np.int64)
            base = chunk_rows * (np.cumsum(cc) - cc)
            chunk = row // chunk_rows
            place = base[chunk] + (row - chunk * chunk_rows) * cc[chunk]
        span = int(offsets[-1] - offsets[0])
        idx = np.int32 if max(flat.size, span) < 2 ** 31 and span == total \
            else np.int64
        k = np.arange(total, dtype=idx)
        if span == total:
            src = raw[offsets[0]:offsets[-1]]
        else:                   # a null's offsets span bytes: skip them
            src = raw[k + np.repeat(offsets[:-1] - before, lens)]
        flat[k + np.repeat((place - before).astype(idx), lens)] = src
    rows = chunks * chunk_rows
    lengths = np.zeros(rows, np.int32)
    lengths[:n] = lens
    return flat, caps, _pad_to(validity, rows), lengths


def _strings_from_host(values: np.ndarray, validity_padded: np.ndarray,
                       cap: int) -> ColumnVector:
    """The per-value path: every value encoded (or stringified) by a
    Python call.  What `host_strings` refuses takes it, and the tests
    hold `encode_strings` against it."""
    enc = [(v.encode("utf-8") if isinstance(v, str)
            else (v if isinstance(v, (bytes, bytearray)) else
                  (str(v).encode("utf-8") if v is not None else b"")))
           for v in values]
    n = len(enc)
    lens = np.fromiter((len(e) for e in enc), np.int32, count=n)
    max_len = int(lens.max()) if n else 0
    cc = bucket_char_cap(max_len)
    data = np.zeros((cap, cc), np.uint8)
    if n and lens.any():
        flat = np.frombuffer(b"".join(enc), np.uint8)
        starts = np.zeros(n, np.int64)
        np.cumsum(lens[:-1], out=starts[1:])
        row = np.repeat(np.arange(n, dtype=np.int64), lens)
        off = np.arange(len(flat), dtype=np.int64) - np.repeat(starts,
                                                               lens)
        data.reshape(-1)[row * cc + off] = flat
    lengths = np.zeros(cap, np.int32)
    lengths[:n] = lens
    lengths = np.where(validity_padded, lengths, 0).astype(np.int32)
    return ColumnVector(T.STRING, jnp.asarray(data),
                        jnp.asarray(validity_padded), jnp.asarray(lengths))


def align_char_caps(a: ColumnVector, b: ColumnVector
                    ) -> tuple[ColumnVector, ColumnVector]:
    """Pad two string vectors to a shared char capacity (for concat etc.)."""
    assert a.dtype.is_string and b.dtype.is_string
    cc = max(a.char_cap, b.char_cap)
    return _pad_chars(a, cc), _pad_chars(b, cc)


def _pad_chars(v: ColumnVector, cc: int) -> ColumnVector:
    if v.char_cap == cc:
        return v
    pad = jnp.zeros((v.capacity, cc - v.char_cap), jnp.uint8)
    return ColumnVector(v.dtype, jnp.concatenate([v.data, pad], axis=1),
                        v.validity, v.lengths)
