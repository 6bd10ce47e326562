"""String kernels over Arrow offsets+bytes device layout.

Reference analogue: cuDF string kernels used by stringFunctions.scala.
TPU-first: strings have no native XLA type, so every op here is integer
arithmetic over the offsets/bytes buffers — gathers, scatters at row
starts with running sums, and byte-table lookups — all static-shape.
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..columnar.column import StringColumn, bucket_capacity
from ..obs import trace as _obs_trace
from .basic import prefix_max, prefix_sum


def string_lengths(offsets) -> jnp.ndarray:
    return (offsets[1:] - offsets[:-1]).astype(jnp.int32)


def _pack_lanes(offsets, data, num_words: int, num_bytes: int = None):
    """A launch's gathered bytes: rows x bytes a row."""
    return (offsets.shape[0] - 1) * (num_bytes or 8 * num_words)


@_obs_trace.launched(lanes=_pack_lanes)
@functools.partial(jax.jit, static_argnames=("num_words", "num_bytes"))
def str_pack_words(offsets, data, num_words: int, num_bytes: int = None):
    """[cap, num_words] big-endian uint64 words of each string, zero-padded.

    ``num_bytes`` (static; None = all ``8 * num_words``) is how many
    leading bytes a row the program gathers: a caller that holds a bound
    on the strings' bytes (``key_byte_bound``) pays one gathered index a
    byte of the bound, not eight a word.  Every row whose string is
    within ``num_bytes`` gets the same bits either way; a longer string
    is cut at ``num_bytes``."""
    cap = offsets.shape[0] - 1
    starts = offsets[:-1]
    lens = offsets[1:] - starts
    if num_bytes is not None and num_bytes < num_words * 8:
        return _pack_leading_bytes(starts, lens, data, num_words, num_bytes)
    # byte index matrix [cap, num_words*8]
    k = jnp.arange(num_words * 8, dtype=jnp.int32)
    idx = starts[:, None] + k[None, :]
    inb = k[None, :] < lens[:, None]
    byts = jnp.where(inb, jnp.take(data, jnp.clip(idx, 0, data.shape[0] - 1)),
                     jnp.uint8(0)).astype(jnp.uint64)
    w = byts.reshape(cap, num_words, 8)
    shifts = jnp.uint64(8) * (jnp.uint64(7) - jnp.arange(8, dtype=jnp.uint64))
    words = jnp.sum(w << shifts[None, None, :], axis=-1, dtype=jnp.uint64)
    return words


def _pack_leading_bytes(starts, lens, data, num_words: int, num_bytes: int):
    """``str_pack_words`` from the first ``num_bytes`` bytes a row: one
    1-D gather a byte, the bytes assembled as 32-bit halves (the chip's
    64-bit integer is a pair of them), the rest of the words zero."""
    cap = starts.shape[0]
    last = data.shape[0] - 1
    halves = [jnp.zeros(cap, jnp.uint32) for _ in range(2 * num_words)]
    for j in range(num_bytes):
        byte = jnp.where(j < lens,
                         jnp.take(data, jnp.clip(starts + j, 0, last)),
                         jnp.uint8(0))
        halves[j // 4] |= byte.astype(jnp.uint32) << (8 * (3 - j % 4))
    halves = [h.astype(jnp.uint64) for h in halves]
    return jnp.stack([(halves[2 * w] << jnp.uint64(32)) | halves[2 * w + 1]
                      for w in range(num_words)], axis=1)


def needed_key_words(col: StringColumn, num_rows: int) -> int:
    """Bucketed uint64 word count needed to encode this column's strings
    (``key_byte_bound`` in words, rounded up to a power of two)."""
    return bucket_words(key_byte_bound(col, num_rows))


def bucket_words(byte_bound: int) -> int:
    num_words = max(1, -(-byte_bound // 8))
    return 1 << (num_words - 1).bit_length()


def key_byte_bound(col: StringColumn, num_rows: int) -> int:
    """A host-known upper bound on the byte length of this column's
    live strings.

    Uses the column's host-known ``max_bytes`` bound when present; a
    column derived purely on device pays ONE offsets sync and caches
    the bound on the instance (each uncached call would otherwise
    serialize behind all pending device work)."""
    from ..columnar.column import GatheredStringColumn
    if type(col) is GatheredStringColumn and col._mat is None:
        # lazy gather view: bound from the SOURCE without materializing
        # (view rows are a subset of source rows).  Prefer the source's
        # cached live bound over full capacity — stale offsets past a
        # shrunk source's live rows must not inflate the bucket here
        # any more than they may in the non-view path below.
        src = col.src
        if src.max_bytes is None:
            cached = getattr(src, "_live_max_bytes", None)
            if cached is not None:
                return key_byte_bound(src, cached[0])
        return key_byte_bound(src, src.capacity)
    max_len = col.max_bytes
    if max_len is None:
        if not isinstance(num_rows, (int, np.integer)):
            # a device/lazy row count (batch.rows_dev): the live-bound
            # scan below needs the concrete value — one declared pull
            from ..analysis import residency  # lazy: avoids import cycle
            with residency.declared_transfer(site="strings_prep"):
                num_rows = int(num_rows)
        cached = getattr(col, "_live_max_bytes", None)
        if cached is not None and cached[0] >= num_rows:
            max_len = cached[1]
        else:
            from ..analysis import residency  # lazy: avoids import cycle
            with residency.declared_transfer(site="strings_prep"):
                lens = np.asarray(col.offsets[1:]) - np.asarray(
                    col.offsets[:-1])
            # restrict to live rows: stale offsets beyond num_rows (a
            # shrunk batch) must not inflate the bucket
            max_len = int(lens[:num_rows].max()) if num_rows else 0
            col._live_max_bytes = (num_rows, max_len)
    return max_len


def pack_words(col: StringColumn, num_words: int, byte_bound: int = None):
    """``str_pack_words`` over a column: the one place the program is
    launched from.  ``byte_bound`` (or None: no bound at hand) is a
    host-known bound on the bytes of the strings the caller reads
    (``key_byte_bound``); rounded up to a power of two it is what the
    program gathers a row, when that is under the words'
    ``8 * num_words``."""
    num_bytes = None
    if byte_bound is not None:
        num_bytes = 1 << max(0, byte_bound - 1).bit_length()
        if num_bytes >= 8 * num_words:
            num_bytes = None
    return str_pack_words(col.offsets, col.data, num_words, num_bytes)


def string_key_words(col: StringColumn, num_rows: int,
                     num_words: int = None,
                     byte_bound: int = None) -> List[jnp.ndarray]:
    """uint64 key words for sort/group/join: byte words + length tiebreak.

    ``num_words`` must be agreed across batches that will be compared
    against each other (joins unify via needed_key_words over both sides).
    ``byte_bound``: ``key_byte_bound`` of the rows the caller reads,
    where it holds one (``pack_words``); the words are the same bits.
    """
    if num_words is None:
        # max length is host-known from offsets (one small sync per batch;
        # the reference similarly reads cuDF column metadata host-side).
        if byte_bound is None:
            byte_bound = key_byte_bound(col, num_rows)
        num_words = bucket_words(byte_bound)
    words = pack_words(col, num_words, byte_bound)
    out = [words[:, i] for i in range(num_words)]
    out.append(string_lengths(col.offsets).astype(jnp.uint64))
    return out


@_obs_trace.launched()
@jax.jit
def str_gather_offsets(offsets, validity, indices, live=None):
    starts = offsets[:-1]
    lens = offsets[1:] - starts
    ncap = indices.shape[0]
    src = jnp.clip(indices, 0, starts.shape[0] - 1)
    glens = jnp.take(lens, src)
    gvalid = jnp.take(validity, src)
    if live is not None:
        # dead output lanes (gather pads them with a repeated index)
        # must contribute zero bytes, or the no-sync unique-gather byte
        # bound below does not hold
        gvalid = gvalid & live
    glens = jnp.where(gvalid, glens, 0)
    new_offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), prefix_sum(glens).astype(jnp.int32)])
    total = new_offsets[-1]
    return new_offsets, gvalid, jnp.take(starts, src), total


def _materialize_lanes(data, new_offsets, src_starts, out_bytes: int):
    """A launch's lanes: the bytes it lays out, live and dead alike."""
    return out_bytes


@_obs_trace.launched(lanes=_materialize_lanes)
@functools.partial(jax.jit, static_argnames=("out_bytes",))
def str_materialize_bytes(data, new_offsets, src_starts, out_bytes: int):
    """``uint8[out_bytes]``: row ``r``'s bytes ``data[src_starts[r]:]``
    laid at ``new_offsets[r] .. new_offsets[r + 1]``, zero past
    ``new_offsets[-1]``.  ``new_offsets`` starts at 0 and never falls.

    Linear in rows + lanes: lane ``j`` of row ``r`` reads byte
    ``j + shift[r]`` with ``shift = src_starts - new_offsets[:-1]``, so
    each row scatters the step ``shift[r] - shift[r - 1]`` at its first
    lane (empty rows share a lane and their steps add up) and a running
    sum over the lanes hands every lane its row's shift.  One gathered
    byte a lane is then all the per-lane work: the chip pays per gathered
    index, 9 ns, so a search for the lane's row would cost twenty times
    the copy (PERF.md section 5, PR 33)."""
    starts = new_offsets[:-1].astype(jnp.int32)
    shift = src_starts.astype(jnp.int32) - starts
    step = shift - jnp.concatenate([jnp.zeros(1, jnp.int32), shift[:-1]])
    lane_shift = prefix_sum(jnp.zeros(out_bytes, jnp.int32).at[starts].add(
        step, indices_are_sorted=True, mode="drop"))
    j = jnp.arange(out_bytes, dtype=jnp.int32)
    live = j < new_offsets[-1]
    return jnp.where(live,
                     jnp.take(data,
                              jnp.clip(j + lane_shift, 0, data.shape[0] - 1)),
                     jnp.uint8(0))


def gather_strings(offsets, data, validity, indices, live=None,
                   unique=False, max_bytes=None):
    """Row gather for string columns.

    Sizing the output byte buffer needs a host-known bound.  The
    default is the exact total — one device sync per gather (a full
    dispatch-queue drain).  Two SYNC-FREE
    static bounds are used when available:

    - ``unique=True``: every live output lane reads a distinct source
      row, so output bytes <= the source buffer — sort permutations,
      filter compactions and aggregate representative gathers (callers
      must pass ``live`` when their index vector pads dead lanes with
      a repeated index).
    - ``max_bytes``: rows * max-single-string-length, used when that
      bound is not much larger than the source buffer.
    """
    new_offsets, gvalid, src_starts, total = str_gather_offsets(
        offsets, validity, indices, live)
    # str_materialize_bytes does O(out_bytes) device work, so a static
    # bound only beats the ~0.1-0.2s sync when it is SMALL; large
    # source buffers keep the exact-size sync
    _NOSYNC_MAX = 1 << 22
    src_bytes = max(int(data.shape[0]), 1)
    mb_bound = indices.shape[0] * max_bytes if max_bytes else None
    if unique and src_bytes <= _NOSYNC_MAX:
        out_bytes = src_bytes
        if mb_bound is not None:
            out_bytes = min(out_bytes, bucket_capacity(max(1, mb_bound)))
    elif mb_bound is not None and mb_bound <= _NOSYNC_MAX:
        out_bytes = bucket_capacity(max(1, mb_bound))
    else:
        from ..analysis import residency  # lazy: avoids import cycle
        with residency.declared_transfer(site="size_probe"):
            out_bytes = bucket_capacity(max(1, int(total)))
    buf = str_materialize_bytes(data, new_offsets, src_starts, out_bytes)
    return new_offsets, buf, gvalid


# ---------------------------------------------------------------------------
# value kernels
# ---------------------------------------------------------------------------

_UPPER_TBL = np.arange(256, dtype=np.uint8)
_UPPER_TBL[ord("a"): ord("z") + 1] -= 32
_LOWER_TBL = np.arange(256, dtype=np.uint8)
_LOWER_TBL[ord("A"): ord("Z") + 1] += 32


@_obs_trace.launched()
@jax.jit
def str_upper_bytes(data):
    return jnp.take(jnp.asarray(_UPPER_TBL), data.astype(jnp.int32))


@_obs_trace.launched()
@jax.jit
def str_lower_bytes(data):
    return jnp.take(jnp.asarray(_LOWER_TBL), data.astype(jnp.int32))


def upper(col: StringColumn) -> StringColumn:
    return StringColumn(col.offsets, str_upper_bytes(col.data), col.validity,
                        max_bytes=col.max_bytes)


def lower(col: StringColumn) -> StringColumn:
    return StringColumn(col.offsets, str_lower_bytes(col.data), col.validity,
                        max_bytes=col.max_bytes)


@_obs_trace.launched()
@jax.jit
def str_substring_offsets(offsets, start, length):
    """Spark substring semantics: 1-based start, negative counts from end."""
    starts = offsets[:-1]
    lens = offsets[1:] - starts
    s = jnp.where(start > 0, start - 1,
                  jnp.where(start < 0, jnp.maximum(lens + start, 0), 0))
    s = jnp.minimum(s, lens)
    l = jnp.clip(length, 0, lens - s)
    return (starts + s).astype(jnp.int32), l.astype(jnp.int32)


def substring(col: StringColumn, start: int, length: int) -> StringColumn:
    cap = col.capacity
    start_a = jnp.full((cap,), start, jnp.int32)
    len_a = jnp.full((cap,), length if length is not None else 2**31 - 1,
                     jnp.int32)
    src_starts, new_lens = str_substring_offsets(col.offsets, start_a, len_a)
    new_lens = jnp.where(col.validity, new_lens, 0)
    new_offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(new_lens).astype(jnp.int32)])
    from ..analysis import residency  # lazy: avoids import cycle
    with residency.declared_transfer(site="size_probe"):
        total = int(new_offsets[-1])
    out_bytes = bucket_capacity(max(1, total))
    buf = str_materialize_bytes(col.data, new_offsets, src_starts, out_bytes)
    mb = col.max_bytes
    if mb is not None and length is not None:
        mb = min(mb, max(length, 0))
    return StringColumn(new_offsets, buf, col.validity, max_bytes=mb)


def char_length(col: StringColumn) -> jnp.ndarray:
    """UTF-8 code point count (Spark length()): count non-continuation bytes."""
    is_cont = (col.data & jnp.uint8(0xC0)) == jnp.uint8(0x80)
    cont_cum = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(is_cont.astype(jnp.int32))])
    ends = jnp.clip(col.offsets[1:], 0, cont_cum.shape[0] - 1)
    begs = jnp.clip(col.offsets[:-1], 0, cont_cum.shape[0] - 1)
    byte_len = col.offsets[1:] - col.offsets[:-1]
    cont = jnp.take(cont_cum, ends) - jnp.take(cont_cum, begs)
    return (byte_len - cont).astype(jnp.int32)


def byte_length(col: StringColumn) -> jnp.ndarray:
    return (col.offsets[1:] - col.offsets[:-1]).astype(jnp.int32)


def _like_lanes(offsets, data, segs):
    """A launch's lanes: the string bytes it scans, which are also the
    counter ``str.like.bytes``; ``str.like.rows`` adds its rows."""
    _obs_trace.count("str.like.bytes", data.shape[0])
    _obs_trace.count("str.like.rows", offsets.shape[0] - 1)
    return data.shape[0]


@_obs_trace.launched(lanes=_like_lanes)
@functools.partial(jax.jit, static_argnames=("segs",))
def str_like_match(offsets, data, segs):
    """``bool[cap]``: does row ``r``'s string match the LIKE pattern
    whose pieces between ``%`` are ``segs`` (two or more byte strings:
    ``(b"ab", b"")`` is ``ab%``, ``(b"", b"x", b"y", b"")`` is
    ``%x%y%``; no ``_`` and no escape)?

    One pass over the bytes for all the pieces, in the greedy order
    SQL's ``%`` allows: the head at the row's start, the tail at its
    end, the pieces between them left to right without overlap.  A
    piece's occurrences at every byte come from comparing shifted
    slices of the byte buffer with its bytes; a running maximum of the
    positions where the piece may stand (``prefix_max``) tells each
    later position, and each row's end, where the nearest one is.  An
    occurrence counts for a row only from that row's own bytes: the
    last piece must end by the row's tail, and with two pieces or more
    each must follow the one before inside its row, whose start every
    byte learns from a scatter at the row starts and the same running
    maximum.  No gather a byte, no search of a byte's row, no
    ``cumsum``: the chip pays per gathered index (PERF.md section 5)."""
    n = data.shape[0]
    head, tail = segs[0], segs[-1]
    mids = [s for s in segs[1:-1] if s]
    starts = offsets[:-1].astype(jnp.int32)
    lo = starts + len(head)                         # first byte past the head
    hi = offsets[1:].astype(jnp.int32) - len(tail)  # first byte of the tail
    ok = lo <= hi
    if n == 0:
        return ok & (not head) & (not tail) & (not mids)
    width = max(len(s) for s in segs)
    padded = jnp.concatenate([data, jnp.zeros(width, data.dtype)])
    pos = jnp.arange(n, dtype=jnp.int32)

    def found(seg):
        eq = jnp.ones(n, bool)
        for k, c in enumerate(seg):
            eq = eq & (padded[k:k + n] == jnp.uint8(c))
        return eq

    def at(x, idx):
        return jnp.take(x, jnp.clip(idx, 0, n - 1))

    if head:
        ok = ok & at(found(head), starts)
    if tail:
        ok = ok & at(found(tail), hi)
    if not mids:
        return ok
    row_start = None
    if len(mids) > 1:
        row_start = prefix_max(jnp.zeros(n, jnp.int32).at[starts].set(
            starts, mode="drop"), 0)
    last, prev_len = None, 0
    for i, seg in enumerate(mids):
        may = found(seg)
        if row_start is not None:
            if i == 0:
                may = may & (pos >= row_start + len(head))
            else:
                before = jnp.concatenate([jnp.full(prev_len, -1, jnp.int32),
                                          last[:n - prev_len]])
                may = may & (before >= row_start)
        last = prefix_max(jnp.where(may, pos, -1), -1)
        prev_len = len(seg)
    end = hi - prev_len
    return ok & (end >= 0) & (at(last, end) >= lo)


def like(col: StringColumn, segs) -> jnp.ndarray:
    """``str_like_match`` over a column: the one place the program is
    launched from."""
    return str_like_match(col.offsets, col.data, tuple(segs))


def starts_with(col: StringColumn, prefix: bytes) -> jnp.ndarray:
    return like(col, (prefix, b""))


def ends_with(col: StringColumn, suffix: bytes) -> jnp.ndarray:
    return like(col, (b"", suffix))


def contains(col: StringColumn, needle: bytes) -> jnp.ndarray:
    return like(col, (b"", needle, b""))
