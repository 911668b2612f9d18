"""Sortable key encoding + multi-key argsort (TPU groupby/sort substrate).

The reference leans on cuDF's `Table.orderBy` / groupby radix machinery;
on TPU the idiomatic equivalent is: encode every key column into one or
more totally-ordered integer arrays, then `jnp.lexsort` — XLA lowers this
to its sort HLO, which is efficient on VPU.

Encodings (all yield uint64/int16 keys whose integer order == SQL order):
  - signed ints/dates/timestamps: bias by the sign bit.
  - floats: IEEE754 total-order trick; NaN encodes above +inf which is
    exactly Spark's "NaN is largest" ordering, and -0.0 < 0.0.
  - bools: 0/1.
  - strings: one int16 key per byte position, +1 biased so "beyond end of
    string" (0) sorts before any real byte — prefix < longer string.
  - nulls: a separate 0/1 rank key ahead of the value keys.
  - invalid rows (padding beyond num_rows): forced to sort last via the
    most-significant key.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.vector import ColumnVector

_SIGN64 = jnp.uint64(1 << 63)


def _encode_int(data) -> jnp.ndarray:
    """signed int/bool -> uint64 whose unsigned order matches value order."""
    if data.dtype == jnp.bool_:
        return data.astype(jnp.uint64)
    return data.astype(jnp.int64).astype(jnp.uint64) ^ _SIGN64


def _float_keys(data, ascending: bool) -> list[jnp.ndarray]:
    """Floats sort as [is_nan, value] key pairs instead of an IEEE bit
    encode: 64-bit bitcast_convert is unimplemented in the TPU X64-rewrite
    pass, and XLA's sort HLO orders plain floats natively.  NaN gets its
    own most-significant key (Spark: NaN is largest); NaN payloads don't
    affect SQL ordering so collapsing them to one flag is exact."""
    nan = jnp.isnan(data)
    val = jnp.where(nan, jnp.zeros_like(data), data)
    if ascending:
        return [nan.astype(jnp.uint8), val]
    return [(~nan).astype(jnp.uint8), -val]


def encode_key_bits(col: ColumnVector, ascending: bool = True,
                    nulls_first: bool = True
                    ) -> list[tuple[jnp.ndarray, int]]:
    """Sort keys for one column, each with its bit width so
    `packed_lexsort` can pack many keys into few uint64 sort words.
    A width of None marks an unpackable key (float64 values) that must be
    its own sort operand.

    Value bits are NORMALIZED under null (and NaN-payload) rows: the
    null-rank / nan-flag key already places those rows, and zeroing the
    garbage value bits makes encoded-word equality coincide with SQL
    group equality — which lets `sort_with_bounds` derive segment
    boundaries from the packed words with no extra per-key gathers."""
    keys: list = []
    null_rank = jnp.where(col.validity,
                          jnp.uint8(1 if nulls_first else 0),
                          jnp.uint8(0 if nulls_first else 1))
    keys.append((null_rank, 1))
    dt = col.dtype
    valid = col.validity

    def width_int(x, bits, bias):
        x = jnp.where(valid, x.astype(jnp.int64), 0)
        enc = (x + bias).astype(jnp.uint64)
        if not ascending:
            enc = jnp.uint64((1 << bits) - 1) - enc
        return (enc, bits)

    if dt.is_string:
        cc = col.char_cap
        pos = jnp.arange(cc)[None, :]
        b = jnp.where(valid[:, None] & (pos < col.lengths[:, None]),
                      col.data.astype(jnp.int16) + 1, 0)
        if not ascending:
            b = jnp.int16(256) - b
        for j in range(cc):
            keys.append((b[:, j].astype(jnp.uint64), 9))
    elif dt.id == T.TypeId.FLOAT32:
        nan = jnp.isnan(col.data) & valid
        keys.append(((nan if ascending else ~nan).astype(jnp.uint8), 1))
        val = jnp.where(valid & ~nan, col.data,
                        jnp.zeros_like(col.data))
        # -0.0 -> 0.0: SQL groups them together (murmur3 normalizes the
        # same way), and the IEEE bit encode would otherwise separate
        # them — both in sort order and in word-equality boundaries
        val = jnp.where(val == 0.0, jnp.zeros_like(val), val)
        bits = lax.bitcast_convert_type(val, jnp.uint32)
        sign = bits >> jnp.uint32(31)
        # IEEE total-order: negative floats reverse, positives offset
        enc = jnp.where(sign == 1, ~bits,
                        bits | jnp.uint32(0x80000000)).astype(jnp.uint64)
        if not ascending:
            enc = jnp.uint64((1 << 32) - 1) - enc
        keys.append((enc, 32))
    elif dt.is_floating:  # float64: 64-bit bitcast is unavailable on TPU
        nan = jnp.isnan(col.data) & valid
        keys.append(((nan if ascending else ~nan).astype(jnp.uint8), 1))
        val = jnp.where(valid & ~nan, col.data,
                        jnp.zeros_like(col.data))
        keys.append((val if ascending else -val, None))
    elif dt.id == T.TypeId.BOOL:
        enc = jnp.where(valid, col.data, False).astype(jnp.uint64)
        if not ascending:
            enc = jnp.uint64(1) - enc
        keys.append((enc, 1))
    elif dt.id == T.TypeId.INT8:
        keys.append(width_int(col.data, 8, 128))
    elif dt.id == T.TypeId.INT16:
        keys.append(width_int(col.data, 16, 1 << 15))
    elif dt.id in (T.TypeId.INT32, T.TypeId.DATE32):
        keys.append(_enc32(jnp.where(valid, col.data, 0)
                           .astype(jnp.int32), ascending))
    elif col.narrow is not None:
        # int64/timestamp whose values fit int32 (narrow shadow): a
        # 32-bit encode halves the packed sort-word width — 64-bit
        # compare-exchange is the dominant cost of bitonic sorts on
        # this chip
        keys.append(_enc32(jnp.where(valid, col.narrow, 0), ascending))
    else:  # int64 / timestamp
        x = jnp.where(valid, col.data.astype(jnp.int64), 0)
        enc = x.astype(jnp.uint64) ^ _SIGN64
        if not ascending:
            enc = ~enc
        keys.append((enc, 64))
    return keys


def _enc32(x_i32, ascending: bool):
    """int32 -> uint32 sort key in pure 32-bit ops (no int64 bias)."""
    enc = lax.bitcast_convert_type(x_i32, jnp.uint32) ^ jnp.uint32(1 << 31)
    if not ascending:
        enc = ~enc
    return (enc, 32)


#: at or below this many packed words, one variadic sort replaces the
#: per-word LSD chain (fewer networks, no re-gathers); above it the
#: chain keeps XLA:TPU variadic-sort compile time bounded
VARIADIC_MAX_WORDS = 3


def _pack_words_width(keys_msf: list, max_bits: int) -> list:
    """Greedily pack (array, bits) keys MSF->LSF into sort words of at
    most `max_bits`; returns [(array, used_bits-or-None), ...].  A key
    wider than max_bits still gets its own full-width word."""
    words: list = []          # (array, used_bits or None)
    acc, used = None, 0

    def flush():
        nonlocal acc, used
        if acc is not None:
            words.append((acc, used))
            acc, used = None, 0

    for arr, bits in keys_msf:
        if bits is None:
            flush()
            words.append((arr, None))
            continue
        if acc is not None and used + bits <= 32:
            # stay in 32-bit arithmetic while the word fits: 64-bit
            # shifts/ors are several times slower on this chip
            acc = ((acc.astype(jnp.uint32) << jnp.uint32(bits))
                   | arr.astype(jnp.uint32))
            used += bits
        elif acc is not None and used + bits <= max_bits:
            acc = ((acc.astype(jnp.uint64) << jnp.uint64(bits))
                   | arr.astype(jnp.uint64))
            used += bits
        else:
            flush()
            acc, used = arr, bits
    flush()
    return words


def _pack_words(keys_msf: list) -> list:
    """Pack keys into sort words, PREFERRING 32-bit words: 64-bit
    compare-exchange is the sort network's dominant cost on the chip (it
    has no native 64-bit integer compare), so two u32 operands sort
    faster than one u64 word (by how much is not measured on the
    current machine: ROADMAP Queue 1 #9).  The
    32-bit split only applies while the total word count stays within
    the variadic-network budget; past it, wide 64-bit words keep the
    word count (and the LSD chain length) down."""
    w32 = _pack_words_width(keys_msf, 32)
    if len(w32) <= VARIADIC_MAX_WORDS:
        return w32
    return _pack_words_width(keys_msf, 64)


def _narrowed(w, wbits):
    if wbits is not None:
        # sort at the narrowest width that holds the word
        return w.astype(jnp.uint32 if wbits <= 32 else jnp.uint64)
    return w


def _neq_prev(sorted_words, cap: int) -> jnp.ndarray:
    """True where any sorted word differs from its predecessor (the
    word-equality boundary primitive shared by both bounds
    derivations)."""
    acc = jnp.zeros(cap, bool)
    for s in sorted_words:
        acc = acc | (s != jnp.roll(s, 1))
    return acc


def _gather_sorted_words(words, perm):
    """Fallback when the sort didn't emit its sorted operands (LSD
    chain path): gather each packed word through the permutation."""
    return [jnp.take(_narrowed(w, b), perm) for w, b in words]


def _sort_words(words: list, cap: int) -> jnp.ndarray:
    """Stable argsort by packed words, most significant first."""
    return _sort_words_full(words, cap)[0]


def _sort_words_full(words: list, cap: int):
    """Stable argsort by packed words, most significant first.
    Returns (perm, sorted_words-or-None): the variadic network emits
    the SORTED key operands as a byproduct — callers that need
    word-equality boundaries use them directly instead of paying one
    random-access gather per word (~70ns/row on this chip)."""
    perm = jnp.arange(cap, dtype=jnp.int32)
    if len(words) <= VARIADIC_MAX_WORDS:
        # one variadic sort network beats the per-word chain ~2x at
        # multi-M rows (measured: 3 words 93ms vs 186ms at 4M) AND
        # skips the per-pass key re-gathers; kept to few operands
        # because XLA:TPU variadic-sort compile time grows steeply
        # with operand count
        # the iota rides as the LAST KEY of an unstable sort: it is
        # unique, so the order is the stable one, and XLA:TPU is spared
        # the tie-break operand a stable sort adds (compiled for a
        # described v5e at 64K rows: 3 u32 words + iota 62 s this way,
        # 119 s as a stable 3-key sort)
        ops = tuple(_narrowed(w, b) for w, b in words) + (perm,)
        out = lax.sort(ops, num_keys=len(ops), is_stable=False)
        return out[-1], list(out[:-1])
    for w, wbits in reversed(words):
        kw = jnp.take(_narrowed(w, wbits), perm)
        _, perm = lax.sort((kw, perm), num_keys=1, is_stable=True)
    return perm, None


def packed_lexsort(keys_msf: list[tuple[jnp.ndarray, int]]) -> jnp.ndarray:
    """Stable multi-key argsort, most-significant key first.

    XLA:TPU sort compile time grows steeply with operand count and row
    count (a 10-operand variadic sort at 64K rows compiles for minutes),
    so keys are greedily packed MSF->LSF into uint64 words and the sort
    runs as one variadic network (few words) or a chain of 1-key stable
    sorts from the least significant word up (the LSD composition)."""
    cap = keys_msf[0][0].shape[0]
    return _sort_words(_pack_words(keys_msf), cap)


def sort_with_bounds(key_cols: list, row_mask: jnp.ndarray,
                     prefix: int = None):
    """Argsort by (column, ascending, nulls_first) keys AND derive
    segment boundaries from the PACKED SORT WORDS — encoded value bits
    are null/NaN-normalized, so word equality == SQL group equality and
    no per-key-column boundary gathers are needed (each costs ~30ms at
    2M rows on this chip; the words are gathered once for small counts).

    `prefix` (default: all keys) marks how many leading key columns
    form the GROUPING; packing never shares a word across the prefix
    border.  Returns (perm, sorted_valid, prefix_bounds, all_bounds);
    invalid rows sort last and never start a segment."""
    cap = row_mask.shape[0]
    if prefix is None:
        prefix = len(key_cols)
    lead = [((~row_mask).astype(jnp.uint8), 1)]
    for col, asc, nf in key_cols[:prefix]:
        lead.extend(encode_key_bits(col, asc, nf))
    rest: list = []
    for col, asc, nf in key_cols[prefix:]:
        rest.extend(encode_key_bits(col, asc, nf))
    # the 32-bit word preference (see _pack_words) must be decided over
    # the COMBINED word count — prefix and rest ride one sort network
    pwords = _pack_words_width(lead, 32)
    rwords = _pack_words_width(rest, 32)
    if len(pwords) + len(rwords) > VARIADIC_MAX_WORDS:
        pwords = _pack_words_width(lead, 64)
        rwords = _pack_words_width(rest, 64)
    perm, swords = _sort_words_full(pwords + rwords, cap)
    # invalid rows sort LAST (the lead word's MSB is the invalid flag),
    # so the sorted mask is a plain prefix — no gather needed
    sorted_valid = jnp.arange(cap) < row_mask.sum()

    if swords is None:
        swords = _gather_sorted_words(pwords + rwords, perm)
    first = jnp.arange(cap) == 0
    pneq = _neq_prev(swords[:len(pwords)], cap)
    prefix_bounds = sorted_valid & (pneq | first)
    if rwords:
        all_bounds = sorted_valid & \
            (pneq | _neq_prev(swords[len(pwords):], cap) | first)
    else:
        all_bounds = prefix_bounds
    return perm, sorted_valid, prefix_bounds, all_bounds


def _key_bit_widths(col) -> list:
    """Per-key bit widths `encode_key_bits` would emit for one column
    (None = unpackable float64 value word).  Kept adjacent to
    `encode_key_bits`' dtype dispatch — the two tables must agree for
    the routing estimate to match the real encode."""
    dt = col.dtype
    out = [1]  # null rank
    if dt.is_string:
        out += [9] * col.char_cap
    elif dt.id == T.TypeId.FLOAT32:
        out += [1, 32]
    elif dt.is_floating:
        out += [1, None]
    elif dt.id == T.TypeId.BOOL:
        out += [1]
    elif dt.id == T.TypeId.INT8:
        out += [8]
    elif dt.id == T.TypeId.INT16:
        out += [16]
    elif dt.id in (T.TypeId.INT32, T.TypeId.DATE32):
        out += [32]
    elif col.narrow is not None:
        out += [32]
    else:
        out += [64]
    return out


def estimate_packed_words(key_cols) -> int:
    """STATIC count of the packed sort words `sort_with_bounds` would
    need for (column, asc, nulls_first) keys — usable at kernel-build
    time to route wide key sets (string groupers explode into one
    9-bit key per char position) to the hash-grouping lane before
    paying the encode.  Simulates `_pack_words`' greedy rule exactly
    (keys never split across words; unpackable float64 flushes), so
    the estimate can't drift low and strand wide keys on the slow
    lane."""
    widths = [1]  # invalid-rows lead flag
    for col, _asc, _nf in key_cols:
        widths.extend(_key_bit_widths(col))
    words, used = 0, 0
    for bits in widths:
        if bits is None:           # unpackable: own word, flush first
            words += 1 if used else 0
            words += 1
            used = 0
        elif used and used + bits <= 64:
            used += bits
        else:
            words += 1 if used else 0
            used = bits
    return words + (1 if used else 0)


def _grouping_hash(cols, seed: int) -> jnp.ndarray:
    """Row hash for the hash-grouping lane.  NOT Spark's Murmur3Hash:
    Spark chains a null as the unchanged seed, which makes shifted
    null patterns — (NULL, x) vs (x, NULL) — collide DETERMINISTICALLY
    on every seed and would fire the collision deopt on ordinary
    nullable multi-key data.  Here a null mixes a per-column marker
    into the chain instead, so only genuine 64-bit accidents collide."""
    from spark_rapids_tpu.ops.murmur3 import hash_column, hash_int
    cap = cols[0].capacity
    h = jnp.full(cap, seed, jnp.uint32)
    for i, c in enumerate(cols):
        hc = hash_column(c, h)
        null_mark = jnp.full(cap, (0x9E3779B9 * (i + 1)) & 0xFFFFFFFF,
                             jnp.uint32)
        h = jnp.where(c.validity, hc, hash_int(null_mark, h))
    return h


def hash_sort_bounds(key_cols: list, row_mask: jnp.ndarray):
    """Equality-only grouping: sort rows by TWO murmur3 words instead
    of the full lexicographic key encode, then read exact segment
    boundaries off the ACTUAL key values of adjacent sorted rows
    (`segment_boundaries` — one vectorized compare per key column).

    Group-by needs grouping, not ordering, so this replaces the
    word-chain sort whose width scales with key content (a 15-column
    string grouper is ~100 packed words ⇒ a 100-pass sort chain whose
    XLA compile alone runs minutes and allocates GBs; TPC-DS q64).
    The murmur3 lane is 2 words for ANY key set.

    SQL-equal keys always hash equal (`ops/murmur3.hash_column`
    canonicalizes NaN / -0.0 and chains nulls as the unchanged seed),
    so a group can only fragment when two DIFFERENT key tuples collide
    on both 32-bit hashes.  That case is detected exactly — a key
    boundary with no hash change — and returned as a deferred flag the
    caller turns into a deopt check (reference analog: cuDF hash
    groupby under `aggregate.scala:312`, which also trades order for
    equality).

    Returns (perm, sorted_valid, bounds, collision_flag)."""
    cols = [c for c, _asc, _nf in key_cols]
    perm, sorted_valid, bounds, _all, collision = \
        hash_prefix_sort_bounds(cols, [], row_mask)
    return perm, sorted_valid, bounds, collision


class _WidthOnly:
    """Dtype/width stand-in for `estimate_packed_words` when a key is
    a computed expression (no backing column to inspect)."""
    __slots__ = ("dtype", "narrow", "char_cap")

    def __init__(self, dtype, narrow=None):
        self.dtype, self.narrow, self.char_cap = dtype, narrow, 0


#: past this many estimated packed sort words a GROUPING key set
#: routes through the 2-word murmur3 hash lane (see hash_sort_bounds)
HASH_GROUP_MIN_WORDS = 4


def wide_key_set(bound_exprs, batch, schema,
                 threshold: int = HASH_GROUP_MIN_WORDS) -> bool:
    """Shared lane routing for grouping sorts (aggregate group-by,
    window partition-by): True when the lexicographic encode of these
    bound key expressions would exceed `threshold` packed words."""
    pseudo = []
    for e in bound_exprs:
        ordinal = getattr(e, "ordinal", None)
        if ordinal is not None and batch is not None:
            pseudo.append((batch.columns[ordinal], True, True))
            continue
        dt = e.data_type(schema)
        if dt.is_string:
            return True  # computed string key: always wide
        pseudo.append((_WidthOnly(dt), True, True))
    return estimate_packed_words(pseudo) > threshold


def hash_prefix_sort_bounds(part_cols: list, order_keys: list,
                            row_mask: jnp.ndarray):
    """`sort_with_bounds` variant for window-style keys: the PARTITION
    prefix needs grouping only (partitions' relative order is
    unobservable), so it sorts as two murmur3 words regardless of key
    width, while the ORDER keys keep the exact lexicographic encode
    (their order IS the window semantics).  Partition boundaries come
    from the actual adjacent key values; a key boundary without a hash
    change is a genuine 64-bit collision, returned as a deferred deopt
    flag (same contract as hash_sort_bounds).

    Returns (perm, sorted_valid, prefix_bounds, all_bounds,
    collision_flag)."""
    cap = row_mask.shape[0]
    h1 = _grouping_hash(part_cols, 42)
    h2 = _grouping_hash(part_cols, 0x3C6EF372)
    w1 = ((~row_mask).astype(jnp.uint64) << jnp.uint64(32)) \
        | h1.astype(jnp.uint64)
    rest: list = []
    for col, asc, nf in order_keys:
        rest.extend(encode_key_bits(col, asc, nf))
    rwords = _pack_words(rest)
    perm, swords = _sort_words_full([(w1, 33), (h2, 32)] + rwords, cap)
    sorted_valid = jnp.arange(cap) < row_mask.sum()
    first = jnp.arange(cap) == 0
    prefix_bounds = segment_boundaries(part_cols, perm, row_mask)
    if swords is None:
        swords = _gather_sorted_words([(w1, 33), (h2, 32)] + rwords, perm)
    hash_change = _neq_prev(swords[:2], cap)
    collision = jnp.any(prefix_bounds & ~hash_change & ~first)
    if rwords:
        all_bounds = sorted_valid & \
            (prefix_bounds | _neq_prev(swords[2:], cap) | first)
    else:
        all_bounds = prefix_bounds
    return perm, sorted_valid, prefix_bounds, all_bounds, collision


def multi_key_argsort(key_cols: list[tuple[ColumnVector, bool, bool]],
                      row_mask: jnp.ndarray) -> jnp.ndarray:
    """Stable argsort by multiple (column, ascending, nulls_first) keys;
    padded rows sort last.  Returns the permutation."""
    keys_msf: list = [((~row_mask).astype(jnp.uint8), 1)]
    for col, asc, nf in key_cols:
        keys_msf.extend(encode_key_bits(col, asc, nf))
    return packed_lexsort(keys_msf)


#: above this requested size the top_k lane hands over to a payload
#: sort: top_k cost grows with k (k=256K over 1M rows is close to a
#: full sort), while the 1-bit-key payload sort is flat in k
MASKED_POSITIONS_TOPK_MAX = 1 << 15


def masked_positions(mask: jnp.ndarray, size: int,
                     fill_value: int) -> jnp.ndarray:
    """First `size` indices where mask is set, ascending; `fill_value`
    past the set count.  `jnp.nonzero(size=...)` lowers to a serialized
    scatter-add on XLA:TPU (~107ms fused at 2M rows — it was the
    single largest op in the group-by kernel), so:
      - small size: 32-bit top_k over the masked iota (~62ms at 2M)
      - large size: ONE stable 1-bit-key sort carrying the iota as a
        payload operand (payload moves are ~free in the sort network;
        cost is flat in `size` where top_k grows with k)
      - size covering the array (a small batch: its groups are not
        compacted): a running count and one small scatter, in 32 bits.
        `jnp.nonzero(size=...)` is the same in 64 (x64 is on), and its
        int64 cumsum does not compile for the chip inside a
        `lax.cond` branch at 256 to 1,024 rows (the branch's scoped
        VMEM: 19 MB asked of 16; PR 36), where the grouped kernel's
        sort body now lives."""
    cap = mask.shape[0]
    iota = lax.iota(jnp.int32, cap)
    if size * 2 > cap:
        nth = jnp.cumsum(mask, dtype=jnp.int32) - 1
        return jnp.full(size, fill_value, jnp.int32).at[
            jnp.where(mask, nth, size)].set(iota, mode="drop")
    if size <= MASKED_POSITIONS_TOPK_MAX:
        keyv = jnp.where(mask, iota, jnp.iinfo(jnp.int32).max)
        neg, _ = lax.top_k(-keyv, size)
        pos = -neg
        return jnp.where(pos >= cap, fill_value, pos)
    # iota as the last key of an unstable sort == the stable order
    _, sorted_iota = lax.sort([~mask, iota], num_keys=2, is_stable=False)
    count = mask.sum()
    head = sorted_iota[:size]
    return jnp.where(jnp.arange(size) < count, head, fill_value)


def key_rows_differ(col: ColumnVector, v, d, ln, v_o, d_o, ln_o):
    """SQL group INEQUALITY of one key column's rows against other rows
    of it: (validity, data, lengths) of each side, the other side
    broadcastable against the first (the previous sorted row of each
    row, or one leader row for all).  Null equals null, NaN equals NaN
    (and -0.0 equals 0.0), a string's bytes count inside its length
    only and the lengths must be equal.  A string's `d` is its
    `[rows, char_cap]` byte matrix, or the list of its
    `packed_string_words`.  The one definition of a group key's
    equality: `segment_boundaries` reads the sorted lanes' boundaries
    off it and `elect_group_leaders` a row's slot."""
    if isinstance(d, (list, tuple)):
        # a string column as `packed_string_words`: with the lengths,
        # equal words are equal strings
        val_neq = ln != ln_o
        for w, w_o in zip(d, d_o):
            val_neq = val_neq | (w != w_o)
    elif col.dtype.is_string:
        pos = jnp.arange(col.char_cap)[None, :]
        in_a = pos < ln[:, None]
        in_b = pos < ln_o[:, None]
        byte_neq = jnp.where(in_a | in_b,
                             jnp.where(in_a & in_b,
                                       d != d_o, True),
                             False).any(axis=1)
        val_neq = byte_neq | (ln != ln_o)
    elif col.dtype.is_floating:
        # group NaNs together
        both_nan = jnp.isnan(d) & jnp.isnan(d_o)
        val_neq = (d != d_o) & ~both_nan
    else:
        val_neq = d != d_o
    return (v != v_o) | (v & v_o & val_neq)


def packed_string_words(col: ColumnVector) -> list:
    """A string column's bytes as uint32 words, four bytes a word, a
    byte at or past its row's length zeroed: one pass over the byte
    matrix, after which a comparison of rows is a comparison of a few
    1-D words (a round of `elect_group_leaders` over the matrix itself
    was 0.12 ms a key column at 65,536 rows on the chip: PR 36)."""
    pos = jnp.arange(col.char_cap)[None, :]
    inside = jnp.where(pos < col.lengths[:, None], col.data,
                       0).astype(jnp.uint32)
    words = []
    for at in range(0, col.char_cap, 4):
        word = inside[:, at]
        for b in range(1, min(4, col.char_cap - at)):
            word = word | (inside[:, at + b] << (8 * b))
        words.append(word)
    return words


def segment_boundaries(key_cols: list[ColumnVector],
                       perm: jnp.ndarray,
                       row_mask: jnp.ndarray) -> jnp.ndarray:
    """After sorting by perm, True where a new group starts (valid rows
    only).  Equal keys = equal (value, null-flag) pairs; two nulls are
    grouped together (SQL GROUP BY semantics)."""
    cap = perm.shape[0]
    sorted_mask = jnp.take(row_mask, perm)
    diff = jnp.zeros(cap, bool)
    for col in key_cols:
        v = jnp.take(col.validity, perm)
        v_prev = jnp.roll(v, 1)
        if col.dtype.is_string:
            d = jnp.take(col.data, perm, axis=0)
            ln = jnp.take(col.lengths, perm)
            d_prev = jnp.roll(d, 1, axis=0)
            ln_prev = jnp.roll(ln, 1)
        else:
            d = jnp.take(col.data, perm)
            d_prev = jnp.roll(d, 1)
            ln = ln_prev = None
        diff = diff | key_rows_differ(col, v, d, ln, v_prev, d_prev,
                                      ln_prev)
    first = jnp.arange(cap) == 0
    return sorted_mask & (diff | first)


def elect_group_leaders(key_cols: list[ColumnVector],
                        row_mask: jnp.ndarray, max_groups: int):
    """Name a batch's groups without a sort, if it has few: round g
    takes the first live row (`row_mask`) that has no slot yet as the
    group's leader and gives every live row whose keys EQUAL the
    leader's (`key_rows_differ`: exact, no hash) slot g.  The rounds
    end when no live row is left without a slot, or after `max_groups`
    of them, so a batch costs one pass over its key columns a group it
    has, and a batch of many groups `max_groups` passes.

    Returns (slot, leaders, num_groups, overflow): the per-row slot
    (-1: a dead row, or one no round reached), the leaders' row
    indices ([max_groups]; groups in order of first appearance), the
    rounds that found a leader, and whether a live row is still
    without a slot (the batch has more than `max_groups` groups)."""
    cap = row_mask.shape[0]
    iota = lax.iota(jnp.int32, cap)

    def one_row(x, i):
        return lax.dynamic_slice_in_dim(x, i, 1, axis=0)

    def rounds_left(state):
        g, _slot, _leaders, more = state
        return more & (g < max_groups)

    # (validity, data, lengths) a key column, as `key_rows_differ`
    # takes them; a string's bytes packed into words once, not
    # compared as a matrix a round
    parts = [(col.validity, packed_string_words(col), col.lengths)
             if col.dtype.is_string else (col.validity, col.data, None)
             for col in key_cols]

    def a_round(state):
        g, slot, leaders, _ = state
        free = row_mask & (slot < 0)
        lead = jnp.min(jnp.where(free, iota, cap - 1))
        same = free
        for col, part in zip(key_cols, parts):
            theirs = jax.tree_util.tree_map(
                lambda x: one_row(x, lead), part)
            same = same & ~key_rows_differ(col, *part, *theirs)
        return (g + 1, jnp.where(same, g, slot),
                leaders.at[g].set(lead), jnp.any(free & ~same))

    g, slot, leaders, more = lax.while_loop(
        rounds_left, a_round,
        (jnp.int32(0), jnp.full(cap, -1, jnp.int32),
         jnp.zeros(max_groups, jnp.int32), jnp.any(row_mask)))
    return slot, leaders, g, more
