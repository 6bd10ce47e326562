"""Canonical sortable key-word encoding.

Every orderable SQL value is mapped to one or more **uint64 words** whose
unsigned lexicographic order equals the SQL ordering of the values.  Sorts,
group-bys and joins all operate on these words, so there is exactly one
comparison code path on the device and it is pure integer VPU work — the
shape XLA tiles best (SURVEY.md §7 "hard parts": sort-based designs map
better to XLA than open-addressing hash tables).

Encodings:
- signed ints  -> x XOR 0x8000...  (order-preserving bias to unsigned)
- floats       -> IEEE-754 trick: if sign bit set, flip all bits, else set
                  sign bit.  NaNs are canonicalized first (Spark treats all
                  NaNs equal and greater than any other value; -0.0 == 0.0 —
                  reference: NormalizeFloatingNumbers.scala).
- bool/date/timestamp/decimal -> via their integer representation
- strings      -> big-endian uint64 words of the UTF-8 bytes, zero padded,
                  plus a final length word as tie-break (exact byte-wise
                  order == code-point order for UTF-8)
- null handling: a leading null-rank word per key (0/1/2) encodes
  nulls-first/last and pushes rows past num_rows to the very end.
- descending   -> bitwise NOT of every word (reverses unsigned order).
"""
from __future__ import annotations

from typing import List, Tuple

import jax.numpy as jnp

from ..columnar import dtypes as T
from ..columnar.column import Column, StringColumn

# python int (not a jnp scalar): creating device values at import
# time would initialize the backend before sessions configure it
SIGN64 = 0x8000000000000000


class PackedStringKey:
    """A STRING key column inside a traced program: the value words
    ``strings.string_key_words`` packed from the column outside, its
    validity, and the host-known bound on a string's bytes that sized
    them (``strings.key_byte_bound``).  Stands where a key Column stands
    in ``batch_key_words``."""

    dtype = T.STRING

    def __init__(self, words, validity, byte_bound: int):
        self.words = list(words)
        self.validity = validity
        self.byte_bound = byte_bound

    @property
    def capacity(self) -> int:
        return int(self.validity.shape[0])


def _ints_to_words(data, nbits: int):
    x = data.astype(jnp.int64)
    return (x.view(jnp.uint64) if nbits == 64
            else x.astype(jnp.uint64)) ^ jnp.uint64(SIGN64)


def _f32_order_word(x) -> jnp.ndarray:
    """f32 array -> one u64 word whose unsigned order == numeric order
    (NaNs canonicalized greatest, -0.0 == 0.0)."""
    x = jnp.where(jnp.isnan(x), jnp.float32(jnp.nan), x)
    x = jnp.where(x == 0.0, jnp.float32(0.0), x)
    bits = x.view(jnp.uint32)
    sign = (bits & jnp.uint32(0x80000000)) != 0
    w = jnp.where(sign, ~bits, bits | jnp.uint32(0x80000000))
    return w.astype(jnp.uint64)


def _f64_bitcast_supported() -> bool:
    """Real TPUs have no f64 ALU: XLA emulates f64 as an f32 pair and
    cannot lower a 64-bit float bitcast.  CPU (tests, virtual meshes)
    can, and there the single-word encoding is exact for full binary64."""
    import jax
    return jax.default_backend() == "cpu"


def _float_to_words(data) -> List[jnp.ndarray]:
    if data.dtype == jnp.dtype(jnp.float32):
        return [_f32_order_word(data)]
    f64 = data.astype(jnp.float64)
    # canonicalize: all NaNs -> +NaN quiet; -0.0 -> 0.0
    f64 = jnp.where(jnp.isnan(f64), jnp.float64(jnp.nan), f64)
    f64 = jnp.where(f64 == 0.0, jnp.float64(0.0), f64)
    if _f64_bitcast_supported():
        bits = f64.view(jnp.uint64)
        sign = (bits & jnp.uint64(SIGN64)) != 0
        flipped = jnp.where(sign, ~bits, bits | jnp.uint64(SIGN64))
        # +NaN lands above +inf (flip keeps NaN mantissa bits set)
        return [flipped]
    # On chip: the emulated f64 is a double-double (hi, lo) f32 pair, so
    # the exact order of representable values is the lexicographic order
    # of the order-words of (hi, lo, residual) — three u32 bitcasts, each
    # of which the chip CAN do.  The residual word covers the few bits a
    # second rounding can still hold.
    hi64 = f64.astype(jnp.float32).astype(jnp.float64)
    ok = jnp.isfinite(f64) & jnp.isfinite(hi64)
    rem1 = jnp.where(ok, f64 - hi64, 0.0)
    lo64 = rem1.astype(jnp.float32).astype(jnp.float64)
    rem2 = jnp.where(ok, rem1 - lo64, 0.0)
    return [_f32_order_word(f64.astype(jnp.float32)),
            _f32_order_word(rem1.astype(jnp.float32)),
            _f32_order_word(rem2.astype(jnp.float32))]


def column_key_words(col: Column, num_rows: int, *, descending: bool = False,
                     nulls_last: bool = False,
                     str_words: int = None) -> List[jnp.ndarray]:
    """Return the list of uint64 word arrays encoding this column as a key.

    The first word is the null/range rank; the rest are value words.
    """
    cap = col.capacity
    in_range = jnp.arange(cap) < num_rows
    valid = col.validity & in_range
    if nulls_last:
        null_rank = jnp.where(valid, jnp.uint64(0), jnp.uint64(1))
    else:
        null_rank = jnp.where(valid, jnp.uint64(1), jnp.uint64(0))
    # rows past num_rows always sort to the absolute end
    null_rank = jnp.where(in_range, null_rank, jnp.uint64(2))

    words = value_words(col, num_rows, str_words=str_words)
    if descending:
        words = [~w for w in words]
        # null rank is NOT inverted: padding must stay at the end and spark's
        # desc default is nulls_last which the caller passes explicitly.
    # zero out words of invalid rows for determinism
    words = [jnp.where(valid, w, jnp.uint64(0)) for w in words]
    return [null_rank] + words


def _few_rows_of_a_large_source(view) -> bool:
    """A lazy string gather view that reads at most a sixteenth of a
    source of 2^16 rows or more, and whose own bytes
    ``strings.gather_strings`` sizes from ``max_bytes`` without a sync
    (under 2^22).  All of it is host-known, so the choice is part of no
    program's shape."""
    import jax
    src, rows = view.src, int(view.idx.shape[0])
    return (src.capacity >= (1 << 16) and rows * 16 <= src.capacity
            and src.max_bytes is not None
            and rows * src.max_bytes <= (1 << 22)
            and not isinstance(view.idx, jax.core.Tracer))


def value_words(col: Column, num_rows: int, str_words: int = None,
                str_bound: int = None) -> List[jnp.ndarray]:
    """uint64 word list for the column values (no null rank).
    ``str_bound``: for a STRING column, ``strings.key_byte_bound`` of it
    where the caller holds that already (it sizes the byte gather, not
    the words: ``strings.pack_words``)."""
    dt = col.dtype
    if type(col) is PackedStringKey:
        return list(col.words)
    from ..columnar.column import GatheredStringColumn
    if type(col) is GatheredStringColumn and col._mat is None:
        # lazy gather view: gather the SOURCE column's words by index —
        # pure integer device work, no byte materialization and no
        # sizing sync.  num_words from the source's full capacity so
        # every view over one source agrees on word count.  (A bound on
        # the view is one on the source's rows it reads:
        # ``key_byte_bound`` takes it from the source.)
        from . import strings as skern
        src = col.src
        if str_words is None:
            str_bound = skern.key_byte_bound(src, src.capacity)
            str_words = skern.bucket_words(str_bound)
        if _few_rows_of_a_large_source(col):
            # packing every row of the source to gather a sixteenth of
            # the words costs the SOURCE's size a view (a semi join's
            # survivors over a 2^19-row build: 137 ms a launch on the
            # chip); the view's own strings are few, and their bytes are
            # sized without a sync
            return skern.string_key_words(col._materialize(),
                                          col.capacity,
                                          num_words=str_words,
                                          byte_bound=str_bound)
        src_words = skern.string_key_words(src, src.capacity,
                                           num_words=str_words,
                                           byte_bound=str_bound)
        return [jnp.take(w, col.idx, axis=0, mode="clip")
                for w in src_words]
    if isinstance(col, StringColumn):
        from . import strings as skern
        return skern.string_key_words(col, num_rows, num_words=str_words,
                                      byte_bound=str_bound)
    from ..columnar.binary64 import Binary64Column
    if isinstance(col, Binary64Column):
        # exact total-order word straight from the bit pattern (the
        # order_word flip is exact integer work; Spark order: NaN
        # greatest, -0.0 == 0.0)
        from . import binary64 as b64
        return [b64.order_word(col.data).astype(jnp.uint64)]
    if dt == T.BOOL:
        return [col.data.astype(jnp.uint64)]
    if dt.is_integral or isinstance(dt, T.DecimalType) or dt in (T.DATE,
                                                                 T.TIMESTAMP):
        return [_ints_to_words(col.data, 64)]
    if dt.is_fractional:
        return _float_to_words(col.data)
    if dt == T.NULL:
        return [jnp.zeros(col.capacity, jnp.uint64)]
    raise NotImplementedError(f"key encoding for {dt}")


def batch_key_words(cols: List[Column], num_rows: int,
                    descending: List[bool] = None,
                    nulls_last: List[bool] = None,
                    str_words: List[int] = None) -> List[jnp.ndarray]:
    descending = descending or [False] * len(cols)
    nulls_last = nulls_last or [False] * len(cols)
    str_words = str_words or [None] * len(cols)
    out: List[jnp.ndarray] = []
    for c, d, nl, sw in zip(cols, descending, nulls_last, str_words):
        out.extend(column_key_words(c, num_rows, descending=d, nulls_last=nl,
                                    str_words=sw))
    if not out:
        # zero keys: single constant word (everything equal)
        cap = cols[0].capacity if cols else 16
        out = [jnp.zeros(cap, jnp.uint64)]
    return out


def _value_word_bits(col, n_words: int) -> List[Tuple[int, int]]:
    """How many low bits each of a key's ``value_words`` can occupy, and
    for a short string's byte word how far it has to come down first:
    (bits, right shift) per word.  Only what is narrow by construction
    is told apart; everything else is a full 64-bit word."""
    if type(col) is PackedStringKey:
        # byte words are big-endian and zero padded; the last word is
        # the length, at most the byte bound
        bound = max(1, min(col.byte_bound, 8 * (n_words - 1)))
        full = [(64, 0)] * (n_words - 1)
        if n_words == 2 and bound < 8:
            used = 1 << (bound - 1).bit_length()         # 1, 2 or 4 bytes
            full = [(8 * used, 64 - 8 * used)]
        return full + [(bound.bit_length(), 0)]
    if col.dtype == T.BOOL:
        return [(1, 0)]
    return [(64, 0)] * n_words


def group_key_words(cols: List[Column], num_rows, live=None) -> List:
    """``batch_key_words`` for a group-by inside a traced program, with
    adjacent narrow words merged: the 2-bit null ranks, a string's
    length word, the byte word of a string of under 8 bytes, booleans.
    Merging keeps the lexicographic order, so groups and their order
    are those of the unmerged words; it exists because every word is a
    sort pass (two flag-like string keys are six words apart and 22 bits
    together).  ``live`` (or None) marks rows a filter kept: a dead row
    gets rank 2 in the first field and sorts past every group.  A
    single merged word of 32 bits or fewer is returned as uint32."""
    fields = []                                  # (word, bits)
    for c in cols:
        ws = column_key_words(c, num_rows)
        fields.append((ws[0], 2))
        for w, (bits, down) in zip(ws[1:],
                                   _value_word_bits(c, len(ws) - 1)):
            fields.append((w >> jnp.uint64(down), bits))
    if live is not None:
        fields[0] = (jnp.where(live, fields[0][0], jnp.uint64(2)), 2)
    merged, room = [], 0
    for w, bits in fields:
        if merged and bits <= room:
            merged[-1] = (merged[-1] << jnp.uint64(bits)) | w
            room -= bits
        else:
            merged.append(w)
            room = 64 - bits
    if len(merged) == 1 and room >= 32:
        return [merged[0].astype(jnp.uint32)]
    return merged


def words_equal_adjacent(words: List[jnp.ndarray]) -> jnp.ndarray:
    """For sorted word arrays: mask[i] = row i differs from row i-1 (i>0)."""
    diff = jnp.zeros(words[0].shape[0], dtype=bool)
    for w in words:
        prev = jnp.concatenate([w[:1], w[:-1]])
        diff = diff | (w != prev)
    return diff.at[0].set(True)


def words_less(words_a: List[jnp.ndarray], idx_a, words_b: List[jnp.ndarray],
               idx_b) -> jnp.ndarray:
    """Vectorized lexicographic a[idx_a] < b[idx_b] over word lists."""
    lt = jnp.zeros(jnp.broadcast_shapes(jnp.shape(idx_a), jnp.shape(idx_b)),
                   dtype=bool)
    eq = jnp.ones_like(lt)
    for wa, wb in zip(words_a, words_b):
        a = wa[idx_a]
        b = wb[idx_b]
        lt = lt | (eq & (a < b))
        eq = eq & (a == b)
    return lt
