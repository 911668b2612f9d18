"""ColumnarBatch: the unit of execution, mirroring Spark's ColumnarBatch of
`GpuColumnVector`s (reference `GpuColumnVector.java:252-261` converters and
`GpuCoalesceBatches.scala` concat).

A batch is host-orchestrated, but LAZILY so: `num_rows` may be either a
Python int or a device scalar still being computed.  Reading `.num_rows`
materializes (a blocking device round trip), while `.num_rows_i32` /
`.row_mask()` / `.maybe_nonempty()` keep the pipeline asynchronous.  This
is the TPU analog of the reference keeping everything on the CUDA stream
until a deliberate sync (`GpuColumnVector`/stream discipline): dispatches
are asynchronous and syncs block, so the engine syncs only at host exits
(the cost ratio is not measured on the current machine).

Batches can also carry deferred validity `checks` (device bool scalars)
registered by optimistic fast paths — see utils/checks.py.  Host-exit
conversions verify them before results are trusted.
"""
from __future__ import annotations

import contextlib
import functools
import itertools
import threading
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.vector import (
    ColumnVector, _pad_to, _strings_from_host, align_char_caps,
    bucket_capacity, bucket_char_cap, encode_strings, host_narrow,
    host_storage, host_strings, host_validity, string_buffers,
    string_lengths)

#: most host bytes one grouped upload (`ColumnarBatch.chunks_from_numpy`)
#: sends at once.  A whole SF1 lineitem partition (3 M rows of q6's four
#: columns, 132 MB) goes in one; a larger partition goes in several runs
#: of whole chunks, which bounds the whole columns that sit on the device
#: beside their batches until the split has run
UPLOAD_TRANSFER_BYTES = 256 << 20


def _split_chunks(arrays, max_rows: int, char_caps: tuple = ()):
    """The device half of a grouped upload: whole columns, a whole
    number of chunks long, cut into chunks of `max_rows` rows, each
    zero-padded to its bucket capacity as `from_numpy` pads on the host.
    An array that `char_caps` gives a tuple of buckets (None or nothing
    for the others) is a string column's byte matrices laid flat, one
    after another (`encode_strings`): chunk i is the next
    `max_rows x char_caps[i]` bytes.  One compiled program per (chunk
    count, dtypes, max_rows, buckets): the ragged tail of a partition
    does not pass through here, so partitions of any length share it."""
    pad = bucket_capacity(max_rows) - max_rows
    columns = list(itertools.zip_longest(arrays, char_caps))
    rows = next(a.shape[0] for a, caps in columns if not caps)

    def cut(a, caps, i):
        if caps:
            lo = max_rows * sum(caps[:i])
            part = a[lo:lo + max_rows * caps[i]].reshape(max_rows, caps[i])
        else:
            part = a[i * max_rows:(i + 1) * max_rows]
        return jnp.pad(part, [(0, pad)] + [(0, 0)] * (part.ndim - 1)) \
            if pad else part
    return [[cut(a, caps, i) for a, caps in columns]
            for i in range(rows // max_rows)]


_split_chunks.__name__ = "upload_split"       # its name on the device
_split_chunks_jit = jax.jit(_split_chunks, static_argnums=(1, 2))


# ---------------------------------------------------------------------------
# The batch helpers' device programs (compact, slice, concat), named for
# the operator that dispatches them: a device trace then shows
# `jit_join_concat`, `jit_exchange_slice`, `jit_agg_concat` beside the
# operators' own kernels, where a chain of eager `jit__take` /
# `jit_concatenate` / `jit_less` operations told nothing about their
# owner (and each compiled on its own).
_OWNER = threading.local()


@contextlib.contextmanager
def programs_of(owner: str):
    """While open, this thread's batch helpers run as `jit_<owner>_<helper>`
    (`exec/base.kernel_name` spelling).  An operator opens it around
    its OWN helper calls, not around its child's pulls."""
    prev = getattr(_OWNER, "name", None)
    _OWNER.name = owner
    try:
        yield
    finally:
        _OWNER.name = prev


def _program(fn, **jit_kwargs):
    """`fn` jitted under the current owner's name, built once a name."""
    owner = getattr(_OWNER, "name", None) or "batch"
    return _labelled(f"{owner}_{fn.__name__.strip('_')}", fn,
                     tuple(sorted(jit_kwargs.items())))


@functools.lru_cache(maxsize=None)
def _labelled(label: str, fn, jit_kwargs: tuple):
    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = label                  # its name on the device
    return jax.jit(program, **dict(jit_kwargs))


def _dense(columns, sparse, n):
    cap = sparse.shape[0]
    (idx,) = jnp.nonzero(sparse, size=cap, fill_value=cap - 1)
    valid = jnp.arange(cap) < n
    return [c.gather(idx, valid) for c in columns]


def _slice(columns, start, length, cap):
    idx = jnp.arange(cap) + start
    valid = jnp.arange(cap) < length
    return [c.gather(jnp.where(valid, idx, 0), valid) for c in columns]


def _concat(columns, rows, out_cap):
    """`columns`: every input batch's (dense) columns; `rows`: their row
    counts, int32[B].  Output row i maps to input batch
    j = #(cumulative counts <= i) at local row i - start_j: all index
    math on the device against the (small) count vector."""
    caps = [cols[0].capacity for cols in columns]
    cap_offsets = np.concatenate([[0], np.cumsum(caps)[:-1]])
    cum = jnp.cumsum(rows)
    starts = cum - rows
    total = cum[-1]
    i = jnp.arange(out_cap, dtype=jnp.int32)
    bid = (i[:, None] >= cum[None, :]).sum(axis=1)  # cap x B compares
    bid_c = jnp.minimum(bid, len(columns) - 1)
    local = i - jnp.take(starts, bid_c)
    jidx = jnp.take(jnp.asarray(cap_offsets, jnp.int32), bid_c) + local
    valid = i < total
    jidx = jnp.where(valid, jidx, 0)
    cols = []
    for data, validity, lengths, narrow, dtype in _stack_columns(columns):
        cols.append(ColumnVector(
            dtype,
            jnp.take(data, jidx, axis=0, mode="clip"),
            jnp.take(validity, jidx, mode="clip") & valid,
            None if lengths is None else jnp.take(lengths, jidx, mode="clip"),
            None if narrow is None else jnp.take(narrow, jidx, mode="clip")))
    return cols, total


def _concat_sparse(columns, masks, pad):
    def pad_tail(arr, fill=0):
        if not pad or arr is None:
            return arr
        tail_shape = (pad,) + arr.shape[1:]
        return jnp.concatenate([arr, jnp.full(tail_shape, fill, arr.dtype)])

    if pad:
        masks = list(masks) + [jnp.zeros((pad,), bool)]
    cols = [ColumnVector(dtype, pad_tail(data), pad_tail(validity, False),
                         pad_tail(lengths), pad_tail(narrow))
            for data, validity, lengths, narrow, dtype
            in _stack_columns(columns)]
    return cols, jnp.concatenate(masks)


def _rows(arrays: Optional[dict], lo: int, hi: int) -> Optional[dict]:
    return arrays and {k: v[lo:hi] for k, v in arrays.items()}


def _record_upload(cols: list[ColumnVector], rows: int) -> None:
    """Movement ledger: THE host->device construction point — one upload
    record per batch, padded device footprint incl. narrow shadows."""
    from spark_rapids_tpu.utils import movement as MV
    if cols and MV.ledger() is not None:
        MV.record(MV.EDGE_UPLOAD,
                  sum(MV.vector_device_bytes(c) for c in cols),
                  site="batch.from_numpy", rows=rows)


def _upload_strings(data: dict, schema: T.Schema, validity: dict,
                    max_rows: int) -> tuple[dict, int]:
    """The string columns of one run: {name: [the
    `ColumnVector.from_numpy` of each `max_rows` rows]} (`char_cap`
    bucketed per chunk) and the host-to-device arrays sent.  Two full
    chunks or more: each column is encoded once from its Arrow buffers
    (`encode_strings`), all of them go in ONE `device_put` (the body's
    byte matrices laid flat, validity, lengths; the ragged tail padded
    on the host) and the split program cuts the body on the device.
    Fewer: chunk by chunk.  A column that is no Arrow array (Arrow
    refused it: `chunks_from_numpy`) goes value by value, chunk by
    chunk, and is counted in `per_value`.
    One `exec:upload-strings` span holds them all; a schema without a
    string column opens none."""
    fields = [f for f in schema.fields if f.dtype.is_string]
    if not fields:
        return {}, 0
    from spark_rapids_tpu.utils import movement as MV
    from spark_rapids_tpu.utils import profile as P
    n = len(data[fields[0].name])
    body = n - n % max_rows
    tail_cap = bucket_capacity(n - body)
    with P.span(P.SPAN_UPLOAD_STRINGS) as sp:
        out, grouped, host, tails, caps, per_value = {}, [], [], [], [], 0
        for f in fields:
            valid, strings = validity.get(f.name), data[f.name]
            if not isinstance(strings, pa.Array):   # Arrow refused it
                per_value += n
                values = np.asarray(strings)
                out[f.name] = [_strings_from_host(
                    values[lo:hi], _pad_to(host_validity(
                        values[lo:hi], _cut(valid, lo, hi)), cap), cap)
                    for lo, hi, cap in _chunk_bounds(n, max_rows)]
            elif body < 2 * max_rows:
                out[f.name] = [ColumnVector.from_numpy(
                    strings[lo:hi], f.dtype, _cut(valid, lo, hi))
                    for lo, hi, _ in _chunk_bounds(n, max_rows)]
            else:
                offsets, raw = string_buffers(strings)
                valid = host_validity(strings, valid)
                flat, cc, v, lengths = encode_strings(
                    offsets[:body + 1], raw, valid[:body], max_rows)
                host += [flat, v, lengths]
                caps += [cc, None, None]
                if body < n:
                    flat, (cc,), v, lengths = encode_strings(
                        offsets[body:], raw, valid[body:], tail_cap)
                    tails += [flat.reshape(tail_cap, cc), v, lengths]
                grouped.append(f.name)
        transfers = sum(c.device_arrays for cs in out.values()
                        for c in cs) + len(host) + len(tails)
        if host:
            sent = jax.device_put(host + tails)
            chunks = _split_chunks_jit(sent[:len(host)], max_rows,
                                       tuple(caps))
            if tails:
                chunks.append(sent[len(host):])
            del sent
            for k, name in enumerate(grouped):
                out[name] = [ColumnVector(T.STRING, *chunk[3 * k:3 * k + 3])
                             for chunk in chunks]
        if sp is not None:
            cols = [c for chunks in out.values() for c in chunks]
            sp.args = {"columns": len(fields),
                       "chunks": -(-n // max_rows), "rows": n,
                       "device_bytes": sum(MV.vector_device_bytes(c)
                                           for c in cols),
                       "transfers": transfers, "per_value": per_value}
    return out, transfers


def _cut(mask: Optional[np.ndarray], lo: int, hi: int):
    return None if mask is None else mask[lo:hi]


def _chunk_bounds(n: int, max_rows: int):
    """(lo, hi, capacity) of each `max_rows` rows of n."""
    return [(lo, min(lo + max_rows, n),
             bucket_capacity(min(max_rows, n - lo)))
            for lo in range(0, n, max_rows)]


def _upload_run(data: dict, schema: T.Schema, validity: Optional[dict],
                max_rows: int, fixed: list) -> tuple[list, int]:
    """One run of `ColumnarBatch.chunks_from_numpy`: its full chunks as
    views of the whole columns, cut on the device, and its ragged tail
    padded on the host as `from_numpy` pads it, all in one `device_put`;
    then its string columns the same way in a second one
    (`_upload_strings`), encoded while the device takes the first
    transfer and runs its split."""
    n = len(next(iter(data.values())))
    body = n - n % max_rows
    validity = validity or {}
    grouped = body >= 2 * max_rows and bool(fixed)
    chunks, narrowed, transfers = [], set(), 0
    if grouped:
        host = []
        for f in fixed:
            values = np.asarray(data[f.name])
            safe = host_storage(values, f.dtype)
            host += [safe, host_validity(values, validity.get(f.name))]
            narrow = host_narrow(safe, f.dtype)
            if narrow is not None:
                narrowed.add(f.name)
                host.append(narrow)
        tail_cap = bucket_capacity(n - body)
        sent = jax.device_put([a[:body] for a in host] + (
            [_pad_to(a[body:], tail_cap) for a in host] if body < n else []))
        transfers = len(sent)
        whole, tail = sent[:len(host)], sent[len(host):]
        chunks = _split_chunks_jit(whole, max_rows)
        # the whole columns leave the device once the split has run: the
        # source is never held twice for longer than that
        del sent, whole
        if tail:
            chunks.append(tail)
    strings, sent = _upload_strings(data, schema, validity, max_rows)
    transfers += sent
    batches = []
    for i, lo in enumerate(range(0, n, max_rows)):
        rows = min(max_rows, n - lo)
        arrays, cols = iter(chunks[i] if grouped else ()), []
        for f in schema.fields:
            if f.dtype.is_string:
                cols.append(strings[f.name][i])
            elif grouped:
                cols.append(ColumnVector(
                    f.dtype, next(arrays), next(arrays), None,
                    next(arrays) if f.name in narrowed else None))
            else:
                valid = validity.get(f.name)
                cols.append(ColumnVector.from_numpy(
                    np.asarray(data[f.name][lo:lo + rows]), f.dtype,
                    None if valid is None else valid[lo:lo + rows]))
                transfers += cols[-1].device_arrays
        _record_upload(cols, rows)
        batches.append(ColumnarBatch(schema, cols, rows))
    return batches, transfers


def _async_copy(arr) -> None:
    try:
        arr.copy_to_host_async()
    except Exception:
        pass


class ColumnarBatch:
    """schema + padded device columns + (possibly lazy) row count.

    A batch may be SPARSE: `sparse` is a device bool mask selecting the
    live rows (a Velox-style selection vector).  Compaction (nonzero +
    gather) costs ~130ms per 2M rows on TPU, so filters and joins defer
    it: sparse-aware consumers (sort, aggregate, filter, project, join
    probe) fold the mask into their own row masking for free; everyone
    else calls `.dense()` to compact on demand.  For a sparse batch,
    rows [0, num_rows) are NOT contiguous — `num_rows` is the mask
    popcount."""

    __slots__ = ("schema", "columns", "_rows", "checks", "sparse")

    def __init__(self, schema: T.Schema, columns: list[ColumnVector],
                 num_rows, checks: tuple = (), sparse=None):
        self.schema = schema
        self.columns = columns
        self.sparse = sparse
        if num_rows is None:
            assert sparse is not None
            num_rows = jnp.sum(sparse).astype(jnp.int32)
        self._rows = num_rows
        self.checks = tuple(checks)
        assert len(self.columns) == len(self.schema.fields)
        caps = {c.capacity for c in self.columns}
        assert len(caps) <= 1, f"ragged capacities {caps}"

    def dense(self) -> "ColumnarBatch":
        """Compact a sparse batch to the dense rows-first layout (the
        expensive step deferred selection exists to avoid — only host
        exits and position-addressed ops should need it)."""
        if self.sparse is None:
            return self
        n = self.num_rows_i32
        cols = _program(_dense)(self.columns, self.sparse, n)
        rows = self._rows if isinstance(self._rows, int) else n
        return ColumnarBatch(self.schema, cols, rows, self.checks)

    # -- row count (lazy) ---------------------------------------------------
    @property
    def num_rows(self) -> int:
        """Host row count — SYNCS if the count is still a device scalar."""
        if not isinstance(self._rows, int):
            from spark_rapids_tpu.utils import checks as CK
            CK.note_host_sync("batch.num_rows", nbytes=4)
            self._rows = int(np.asarray(self._rows))
        return self._rows

    @num_rows.setter
    def num_rows(self, value):
        self._rows = value

    @property
    def num_rows_known(self) -> bool:
        return isinstance(self._rows, int)

    @property
    def num_rows_i32(self):
        """Row count as an int32 operand for kernels — never syncs.  A
        known count is a host `np.int32` that a jitted call carries with
        its other arguments (a device scalar made of it first is an
        eager dispatch and a 4-byte transfer of its own, 0.54 ms on the
        chip: PERF.md, PR 32); an unknown one is the device scalar.
        Same abstract value either way, so the same program."""
        if isinstance(self._rows, int):
            return np.int32(self._rows)
        return jnp.asarray(self._rows, jnp.int32)

    def maybe_nonempty(self) -> bool:
        """True unless the batch is KNOWN to be empty (no sync)."""
        return not isinstance(self._rows, int) or self._rows > 0

    def prefetch(self) -> None:
        """Start async D2H copies of the row count and all buffers so a
        following host conversion pays ~one round trip, not one per
        array."""
        if not isinstance(self._rows, int):
            _async_copy(self._rows)
        for c in self.columns:
            _async_copy(c.data)
            _async_copy(c.validity)
            if c.lengths is not None:
                _async_copy(c.lengths)

    def verify_checks(self) -> None:
        from spark_rapids_tpu.utils import checks as CK
        CK.verify(self.checks)

    @property
    def capacity(self) -> int:
        return self.columns[0].capacity if self.columns else bucket_capacity(
            self.num_rows)

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def column(self, name_or_idx) -> ColumnVector:
        if isinstance(name_or_idx, str):
            return self.columns[self.schema.index(name_or_idx)]
        return self.columns[name_or_idx]

    def row_mask(self) -> jnp.ndarray:
        if self.sparse is not None:
            return self.sparse
        return jnp.arange(self.capacity) < self.num_rows_i32

    # -- construction -------------------------------------------------------
    @staticmethod
    def from_numpy(data: dict[str, np.ndarray],
                   schema: Optional[T.Schema] = None,
                   validity: Optional[dict[str, np.ndarray]] = None,
                   capacity: Optional[int] = None) -> "ColumnarBatch":
        names = list(data)
        n = len(next(iter(data.values()))) if data else 0
        cap = capacity or bucket_capacity(n)
        cols, fields = [], []
        for name in names:
            dt = schema.field(name).dtype if schema else None
            v = validity.get(name) if validity else None
            values = data[name]
            if not isinstance(values, pa.Array):
                values = np.asarray(values)
            col = ColumnVector.from_numpy(values, dt, v, cap)
            cols.append(col)
            fields.append(T.Field(name, col.dtype))
        # from_arrow / from_pandas funnel through here
        _record_upload(cols, n)
        return ColumnarBatch(schema or T.Schema(tuple(fields)), cols, n)

    @staticmethod
    def chunks_from_numpy(data: dict[str, np.ndarray], schema: T.Schema,
                          validity: Optional[dict[str, np.ndarray]],
                          max_rows: int, device=None
                          ) -> tuple[list["ColumnarBatch"], int]:
        """Host columns -> the batches `from_numpy` gives for each
        `max_rows` rows of them (same rows, capacities and contents), in
        few large transfers; also returns how many host-to-device arrays
        went.

        The columns of a run of chunks go to the device in two
        `device_put`s, the fixed-width ones first: the full chunks whole
        (data, validity and `narrow` once a column, views of the host
        columns; a string column's byte matrices laid flat, its validity
        and lengths, encoded once from its Arrow buffers) for one jitted
        program to cut there, and the ragged tail padded on the host as
        `from_numpy` pads it.  A string column's `char_cap` stays
        bucketed per chunk.  What is grouped follows what the call sees,
        no conf: a run with fewer than two full chunks takes
        `from_numpy`'s path, and so does a string column that Arrow
        refuses, value by value (`host_strings`); a run holds whole
        chunks up to `UPLOAD_TRANSFER_BYTES`.  An INT64 `narrow` shadow
        is decided once a run: there when the whole run fits int32.

        `device` (a partition's chip under an active mesh): the
        transfers and the split program go there and the batches come
        back COMMITTED to it, so every program downstream follows them;
        without it they lie on the default chip, committed nowhere."""
        if device is not None:
            with jax.default_device(device):
                batches, sent = ColumnarBatch.chunks_from_numpy(
                    data, schema, validity, max_rows)
            # on the chip already: committing copies nothing
            cols = jax.device_put([b.columns for b in batches], device)
            return [ColumnarBatch(schema, c, b._rows)
                    for b, c in zip(batches, cols)], sent
        n = len(next(iter(data.values()))) if data else 0
        fixed = [f for f in schema.fields if not f.dtype.is_string]
        # storage + validity + at most a 4-byte shadow
        row_bytes = sum(f.dtype.storage_dtype.itemsize + 5 for f in fixed)
        data = dict(data)
        for f in schema.fields:
            if not f.dtype.is_string:
                continue
            strings = host_strings(data[f.name])
            if strings is not None:
                # the byte matrix at the longest value's bucket +
                # validity + lengths
                data[f.name] = strings
                row_bytes += 5 + bucket_char_cap(int(string_lengths(
                    string_buffers(strings)[0]).max(initial=0)))
        run = max_rows * max(1, UPLOAD_TRANSFER_BYTES // max(
            1, max_rows * row_bytes))
        batches, transfers = [], 0
        for lo in range(0, n, run):
            got, sent = _upload_run(_rows(data, lo, lo + run), schema,
                                    _rows(validity, lo, lo + run),
                                    max_rows, fixed)
            batches += got
            transfers += sent
        return batches, transfers

    @staticmethod
    def from_pandas(df) -> "ColumnarBatch":
        data, validity = {}, {}
        for name in df.columns:
            s = df[name]
            if s.dtype == object or str(s.dtype) in ("string", "str"):
                vals = np.array(
                    [None if v is None or (isinstance(v, float) and np.isnan(v))
                     else v for v in s.tolist()], dtype=object)
                data[name] = vals
            else:
                mask = s.isna().to_numpy()
                arr = s.to_numpy()
                if mask.any() and arr.dtype.kind == "f":
                    arr = np.where(mask, 0.0, arr)
                data[name] = arr
                validity[name] = ~mask
        return ColumnarBatch.from_numpy(data, validity=validity or None)

    @staticmethod
    def from_arrow(table) -> "ColumnarBatch":
        """Arrow table/record-batch → device batch (the scan upload path,
        reference `Table.readParquet` + `GpuColumnVector.from`)."""
        data, validity, fields = {}, {}, []
        for i, name in enumerate(table.schema.names):
            col = table.column(i)
            if hasattr(col, "combine_chunks"):
                col = col.combine_chunks()
            dt = T.from_arrow(col.type)
            fields.append(T.Field(name, dt))
            np_valid = ~np.asarray(col.is_null())
            if dt.is_string:
                data[name] = col        # its buffers are the host form
            elif dt.id == T.TypeId.TIMESTAMP_US:
                import pyarrow.compute as pc
                import pyarrow as pa
                c = col.cast(pa.timestamp("us"))
                arr = c.to_numpy(zero_copy_only=False)
                arr = arr.astype("datetime64[us]").astype(np.int64)
                arr = np.where(np_valid, arr, 0)
                data[name] = arr
            else:
                arr = col.to_numpy(zero_copy_only=False)
                if arr.dtype.kind == "f" and (~np_valid).any():
                    arr = np.where(np_valid, arr, 0.0)
                arr = np.asarray(arr, dt.storage_dtype)
                data[name] = arr
            validity[name] = np_valid
        return ColumnarBatch.from_numpy(
            data, T.Schema(tuple(fields)), validity)

    def _note_readback(self, site: str) -> None:
        """Ledger hook for the host-conversion sinks: the full padded
        device arrays are pulled to the host (to_numpy trims after the
        transfer), so the moved bytes are the device footprint."""
        from spark_rapids_tpu.utils import movement as MV
        if MV.ledger() is not None:
            MV.record(MV.EDGE_READBACK, self.device_size_bytes(),
                      site=site)

    # -- host conversion ----------------------------------------------------
    def to_pandas(self):
        import pandas as pd
        if self.sparse is not None:
            return self.dense().to_pandas()
        self._note_readback("collect.to_pandas")
        self.prefetch()
        self.verify_checks()
        out = {}
        for f, c in zip(self.schema.fields, self.columns):
            vals, validity = c.to_numpy(self.num_rows)
            if f.dtype.is_string:
                out[f.name] = pd.Series(list(vals), dtype=object)
            elif f.dtype.id == T.TypeId.TIMESTAMP_US:
                s = pd.Series(vals.astype("datetime64[us]"))
                s[~validity] = pd.NaT
                out[f.name] = s
            elif validity.all():
                out[f.name] = pd.Series(vals)
            else:
                s = pd.Series(vals).astype(object)
                s[~validity] = None
                out[f.name] = s
        return pd.DataFrame(out)

    def to_pylist(self) -> list[dict]:
        if self.sparse is not None:
            return self.dense().to_pylist()
        self._note_readback("collect.to_pylist")
        self.prefetch()
        self.verify_checks()
        cols = {f.name: c.to_pylist(self.num_rows)
                for f, c in zip(self.schema.fields, self.columns)}
        return [{k: v[i] for k, v in cols.items()}
                for i in range(self.num_rows)]

    def to_arrow(self):
        import pyarrow as pa
        if self.sparse is not None:
            return self.dense().to_arrow()
        self._note_readback("collect.to_arrow")
        self.prefetch()
        self.verify_checks()
        arrays = []
        for f, c in zip(self.schema.fields, self.columns):
            vals, validity = c.to_numpy(self.num_rows)
            if f.dtype.is_string:
                arrays.append(pa.array(list(vals), T.to_arrow(f.dtype)))
            else:
                mask = None if validity.all() else ~validity
                if f.dtype.id == T.TypeId.TIMESTAMP_US:
                    arrays.append(pa.array(vals, pa.int64(), mask=mask).cast(
                        T.to_arrow(f.dtype)))
                else:
                    arrays.append(
                        pa.array(vals, T.to_arrow(f.dtype), mask=mask))
        return pa.table(arrays, names=list(self.schema.names))

    # -- structural ---------------------------------------------------------
    def select(self, names: Iterable[str]) -> "ColumnarBatch":
        names = list(names)
        cols = [self.column(n) for n in names]
        fields = tuple(self.schema.field(n) for n in names)
        return ColumnarBatch(T.Schema(fields), cols, self._rows,
                             self.checks, self.sparse)

    def with_capacity(self, capacity: int) -> "ColumnarBatch":
        if capacity == self.capacity:
            return self
        if self.sparse is not None:
            return self.dense().with_capacity(capacity)
        rows = (min(self._rows, capacity) if self.num_rows_known
                else jnp.minimum(self._rows, capacity))
        return ColumnarBatch(
            self.schema, [c.with_capacity(capacity) for c in self.columns],
            rows, self.checks)

    def gather(self, indices: jnp.ndarray, index_valid: jnp.ndarray,
               new_num_rows) -> "ColumnarBatch":
        assert self.sparse is None, "gather() addresses dense rows"
        cols = [c.gather(indices, index_valid) for c in self.columns]
        return ColumnarBatch(self.schema, cols, new_num_rows, self.checks)

    def slice(self, start: int, length: int) -> "ColumnarBatch":
        """Host-side row slice (reference SlicedGpuColumnVector)."""
        if self.sparse is not None:
            return self.dense().slice(start, length)
        length = max(0, min(length, self.num_rows - start))
        cols = _program(_slice, static_argnames=("cap",))(
            self.columns, start, length, cap=bucket_capacity(length))
        return ColumnarBatch(self.schema, cols, length, self.checks)

    def take_head(self, n: int) -> "ColumnarBatch":
        """First min(n, num_rows) rows at a STATIC bucket(n) capacity,
        without syncing on the row count (limit/top-N building block)."""
        if self.sparse is not None:
            return self.dense().take_head(n)
        cap = bucket_capacity(n)
        if cap >= self.capacity:
            rows = (min(self._rows, n) if self.num_rows_known
                    else jnp.minimum(self.num_rows_i32, n))
            return ColumnarBatch(self.schema, self.columns, rows,
                                 self.checks)
        idx = jnp.arange(cap)
        count = jnp.minimum(self.num_rows_i32, n)
        valid = idx < count
        cols = [c.gather(idx, valid) for c in self.columns]
        rows = min(self._rows, n) if self.num_rows_known else count
        return ColumnarBatch(self.schema, cols, rows, self.checks)

    def device_size_bytes(self) -> int:
        total = 0
        for c in self.columns:
            total += c.data.size * c.data.dtype.itemsize
            total += c.validity.size
            if c.lengths is not None:
                total += c.lengths.size * 4
        return total


def empty_batch(schema: T.Schema) -> ColumnarBatch:
    """Zero-row batch with properly-typed zero-filled columns."""
    from spark_rapids_tpu.columnar.vector import MIN_CAPACITY, MIN_CHAR_CAP
    cols = []
    for f in schema.fields:
        validity = jnp.zeros(MIN_CAPACITY, bool)
        if f.dtype.is_string:
            cols.append(ColumnVector(
                f.dtype, jnp.zeros((MIN_CAPACITY, MIN_CHAR_CAP), jnp.uint8),
                validity, jnp.zeros(MIN_CAPACITY, jnp.int32)))
        else:
            cols.append(ColumnVector(
                f.dtype, jnp.zeros(MIN_CAPACITY, f.dtype.storage_dtype),
                validity))
    return ColumnarBatch(schema, cols, 0)


def rows_made_known(batches: list[ColumnarBatch], site: str,
                    beyond: int = 0) -> int:
    """Bring the row counts of `batches` that are still device scalars
    to the host in ONE stacked device-to-host read, counted as one host
    sync at `site`, and keep them on the batches (what reading each
    `.num_rows` does, one round trip a batch).  `concat_batches` then
    sizes its output by the rows there are.  Asks nothing when every
    count is known, or when the lazy concat's capacity (the bucketed sum
    of the inputs' capacities) is within `beyond`: at a barrier, padding
    of up to one batch costs less than the question.  Returns the reads
    made, 0 or 1."""
    unknown = [b for b in batches if not b.num_rows_known]
    if not unknown or bucket_capacity(
            sum(b.capacity for b in batches)) <= beyond:
        return 0
    from spark_rapids_tpu.utils import checks as CK
    CK.note_host_sync(site, nbytes=4 * len(unknown))
    counts = np.asarray(jnp.stack([b.num_rows_i32 for b in unknown]))
    for b, n in zip(unknown, counts.tolist()):
        b.num_rows = n
    return 1


def concat_batches(batches: list[ColumnarBatch],
                   sparse_ok: bool = False) -> ColumnarBatch:
    """Device-side concat (reference `Table.concatenate`,
    `GpuCoalesceBatches.scala:53`): stack padded columns then gather the
    valid rows of each input into a fresh bucketed batch.

    With every input's row count on the host the output gets the bucket
    of the rows there are.  While any count is still a device scalar,
    the gather indices are computed DEVICE-SIDE (no sync): output
    capacity is then the bucketed sum of input CAPACITIES (the static
    worst case: 4,194,304 slots for q3's 807,274 build rows at SF0.25)
    and the output row count stays lazy; every kernel downstream runs
    at that capacity.  A barrier that holds many such inputs (a join's
    build side, a single-batch coalesce) calls `rows_made_known` first.

    `sparse_ok=True` (callers whose consumer takes deferred-selection
    batches — the aggregate merge kernel, collect's final dense):
    sparse inputs skip their per-input dense() gathers entirely — padded
    columns and selection masks are stacked as-is and the result stays
    sparse, so the whole concat is sequential copies (bandwidth-bound)
    instead of two random-access gather rounds (~70ns/row each on this
    chip)."""
    assert batches
    if len(batches) == 1:
        return batches[0]
    if sparse_ok and any(b.sparse is not None for b in batches):
        return _concat_sparse_batches(batches)
    batches = [b.dense() for b in batches]
    schema = batches[0].schema
    checks = tuple(c for b in batches for c in b.checks)
    if len(batches) > 64:
        # tree-chunked: the bucket-id search materializes an
        # [out_cap, B] compare matrix, which at B=400 inputs of a
        # 26M-row reduce partition reached a 12.8GB intermediate and
        # OOMed HBM at compile time — chunks bound the matrix and
        # recurse on the (few) chunk results
        return concat_batches([concat_batches(batches[i:i + 64])
                               for i in range(0, len(batches), 64)])
    if all(b.num_rows_known for b in batches):
        # tight: the bucket of the rows there are
        total = sum(b.num_rows for b in batches)
        out_cap = bucket_capacity(total)
        rows = np.array([b.num_rows for b in batches], np.int32)
    else:
        # sync-free: the bucketed sum of input CAPACITIES (the static
        # worst case); the output row count stays on the device
        total = None
        out_cap = bucket_capacity(sum(b.capacity for b in batches))
        rows = jnp.stack([b.num_rows_i32 for b in batches])
    if not schema.fields:
        return ColumnarBatch(schema, [], total if total is not None
                             else jnp.sum(rows), checks)
    cols, lazy_total = _program(_concat, static_argnames=("out_cap",))(
        [b.columns for b in batches], rows, out_cap=out_cap)
    return ColumnarBatch(schema, cols,
                         total if total is not None else lazy_total, checks)


def _stack_columns(columns):
    """[(data, validity, lengths, narrow, dtype)] of every column, the
    input batches' stacked in order; `columns` is each batch's list."""
    out_cols = []
    for vecs in zip(*columns):
        dtype = vecs[0].dtype
        if dtype.is_string:
            cc = max(v.char_cap for v in vecs)
            from spark_rapids_tpu.columnar.vector import _pad_chars
            vecs = [_pad_chars(v, cc) for v in vecs]
        data = jnp.concatenate([v.data for v in vecs])
        validity = jnp.concatenate([v.validity for v in vecs])
        lengths = (jnp.concatenate([v.lengths for v in vecs])
                   if vecs[0].lengths is not None else None)
        narrow = (jnp.concatenate([v.narrow for v in vecs])
                  if all(v.narrow is not None for v in vecs) else None)
        out_cols.append((data, validity, lengths, narrow, dtype))
    return out_cols


def _concat_sparse_batches(batches) -> ColumnarBatch:
    """Gather-free concat: stack each input's padded columns and its
    selection mask; the output batch keeps capacity = bucketed sum of
    input capacities with selection still deferred.  Compaction, if a
    consumer needs it, costs the same single gather round dense() always
    costs — so this path strictly saves the per-input dense gathers."""
    schema = batches[0].schema
    checks = tuple(c for b in batches for c in b.checks)
    scap = sum(b.capacity for b in batches)
    masks = [b.sparse if b.sparse is not None else b.row_mask()
             for b in batches]
    total = sum(b.num_rows for b in batches) \
        if all(b.num_rows_known for b in batches) else \
        jnp.sum(jnp.stack([b.num_rows_i32 for b in batches]))
    cols, mask = _program(_concat_sparse, static_argnames=("pad",))(
        [b.columns for b in batches], masks,
        pad=bucket_capacity(scap) - scap)
    return ColumnarBatch(schema, cols, total, checks, sparse=mask)
