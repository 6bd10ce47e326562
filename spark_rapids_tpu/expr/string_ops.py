"""String expressions — reference analogue: stringFunctions.scala and the

string expr registrations in GpuOverrides.scala (Substring, Like, Concat,
Upper/Lower, trim family, StartsWith/EndsWith/Contains, Length).
"""
from __future__ import annotations

import re
from typing import List, Optional

import numpy as np
import jax.numpy as jnp

from ..columnar import dtypes as T
from ..columnar.column import Column, StringColumn
from ..kernels import strings as skern
from .core import Expression, Scalar, Literal, eval_data_valid, as_column


def _eval_string(expr: Expression, batch) -> StringColumn:
    col = as_column(expr.columnar_eval(batch), batch.capacity, batch.num_rows)
    assert isinstance(col, StringColumn), f"expected string, got {col.dtype}"
    return col


class Upper(Expression):
    def __init__(self, child):
        self.children = [child]

    def with_children(self, c):
        return Upper(c[0])

    def dtype(self):
        return T.STRING

    def columnar_eval(self, batch):
        return skern.upper(_eval_string(self.children[0], batch))


class Lower(Expression):
    def __init__(self, child):
        self.children = [child]

    def with_children(self, c):
        return Lower(c[0])

    def dtype(self):
        return T.STRING

    def columnar_eval(self, batch):
        return skern.lower(_eval_string(self.children[0], batch))


class Length(Expression):
    """Character (code point) length, Spark length()."""

    def __init__(self, child):
        self.children = [child]

    def with_children(self, c):
        return Length(c[0])

    def dtype(self):
        return T.INT32

    def columnar_eval(self, batch):
        col = _eval_string(self.children[0], batch)
        return Column(T.INT32, skern.char_length(col), col.validity)


class Substring(Expression):
    """substring(str, pos, len) with literal pos/len (the common SQL shape;

    reference GpuSubstring also requires literal positions)."""

    def __init__(self, child, pos: Expression, length: Optional[Expression]):
        self.children = [child, pos] + ([length] if length is not None else [])

    def with_children(self, c):
        return Substring(c[0], c[1], c[2] if len(c) > 2 else None)

    def dtype(self):
        return T.STRING

    def columnar_eval(self, batch):
        pos = self.children[1]
        assert isinstance(pos, Literal), "substring pos must be literal"
        length = None
        if len(self.children) > 2:
            ln = self.children[2]
            assert isinstance(ln, Literal), "substring len must be literal"
            length = ln.value
        col = _eval_string(self.children[0], batch)
        return skern.substring(col, pos.value, length)


class _LiteralPatternPredicate(Expression):
    """Base for StartsWith/EndsWith/Contains with literal pattern."""

    kernel = None

    def __init__(self, child, pattern: Expression):
        self.children = [child, pattern]

    def with_children(self, c):
        return type(self)(c[0], c[1])

    def dtype(self):
        return T.BOOL

    def columnar_eval(self, batch):
        pat = self.children[1]
        assert isinstance(pat, Literal), f"{self.name} pattern must be literal"
        col = _eval_string(self.children[0], batch)
        if pat.value is None:
            return Column(T.BOOL, jnp.zeros(col.capacity, bool),
                          jnp.zeros(col.capacity, bool))
        mask = type(self).kernel(col, str(pat.value).encode("utf-8"))
        return Column(T.BOOL, mask, col.validity)


class StartsWith(_LiteralPatternPredicate):
    kernel = staticmethod(skern.starts_with)


class EndsWith(_LiteralPatternPredicate):
    kernel = staticmethod(skern.ends_with)


class Contains(_LiteralPatternPredicate):
    kernel = staticmethod(skern.contains)


class Like(Expression):
    """SQL LIKE with literal pattern.

    Every pattern of literal bytes and ``%`` runs on the device as one
    program (``kernels/strings.str_like_match``; the reference treats
    'regexp like a regular string' the same way, GpuOverrides.scala:470);
    ``_`` or an escape falls back to the host regex.
    """

    def __init__(self, child, pattern: Expression, escape: str = "\\"):
        self.children = [child, pattern]
        self.escape = escape

    def with_children(self, c):
        return Like(c[0], c[1], self.escape)

    def dtype(self):
        return T.BOOL

    def columnar_eval(self, batch):
        pat = self.children[1]
        assert isinstance(pat, Literal), "LIKE pattern must be literal"
        col = _eval_string(self.children[0], batch)
        p = str(pat.value)
        # escaped wildcards (literal %% / _) need the unescaping regex
        # path: the byte fast paths would treat the escape as content
        has_special = "_" in p or self.escape in p
        if not has_special:
            if "%" not in p:
                from .predicates import EqualTo
                return EqualTo(self.children[0],
                               Literal(p, T.STRING)).columnar_eval(batch)
            # every %-only pattern ('abc%', '%x%', 'a%b%c'): one device
            # program over the bytes, no host round trip (the weak spot
            # of string filters serializing through the host per batch)
            mask = skern.like(col, [s.encode() for s in p.split("%")])
            return Column(T.BOOL, mask, col.validity)
        # host regex fallback
        _note_host_regex(f"LIKE {p!r}")
        rx = re.compile(_like_to_regex(p, self.escape), re.DOTALL)
        vals, valid = col.to_numpy(batch.num_rows)
        out = np.zeros(col.capacity, bool)
        for i in range(batch.num_rows):
            if valid[i]:
                out[i] = rx.fullmatch(vals[i]) is not None
        return Column(T.BOOL, jnp.asarray(out), col.validity)


def _like_to_regex(pattern: str, escape: str) -> str:
    out = []
    i = 0
    while i < len(pattern):
        ch = pattern[i]
        if ch == escape and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 2
            continue
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
        i += 1
    return "".join(out)


#: host-regex fallback observability (the silent-serialization weak
#: spot): per-process counter + one warning per distinct pattern
HOST_REGEX_EVALS = {"count": 0}
_WARNED_PATTERNS: set = set()


def _note_host_regex(what: str):
    HOST_REGEX_EVALS["count"] += 1
    if what not in _WARNED_PATTERNS:
        _WARNED_PATTERNS.add(what)
        import logging
        logging.getLogger(__name__).warning(
            "host regex path for %s: this batch serializes through the "
            "host string engine (device LIKE covers literal "
            "prefix/suffix/contains/multi-%% patterns)", what)


class RLike(Expression):
    """Regex match (host path; reference gates regex heavily too)."""

    def __init__(self, child, pattern: Expression):
        self.children = [child, pattern]

    def with_children(self, c):
        return RLike(c[0], c[1])

    def dtype(self):
        return T.BOOL

    def columnar_eval(self, batch):
        pat = self.children[1]
        assert isinstance(pat, Literal)
        _note_host_regex(f"RLIKE {pat.value!r}")
        rx = re.compile(str(pat.value))
        col = _eval_string(self.children[0], batch)
        vals, valid = col.to_numpy(batch.num_rows)
        out = np.zeros(col.capacity, bool)
        for i in range(batch.num_rows):
            if valid[i]:
                out[i] = rx.search(vals[i]) is not None
        return Column(T.BOOL, jnp.asarray(out), col.validity)


class ConcatStrings(Expression):
    """concat(s1, s2, ...) — null if any input null (Spark concat)."""

    def __init__(self, *children):
        self.children = list(children)

    def with_children(self, c):
        return ConcatStrings(*c)

    def dtype(self):
        return T.STRING

    def columnar_eval(self, batch):
        from ..columnar.column import bucket_capacity
        from ..kernels.strings import str_materialize_bytes
        cols = [_eval_string(c, batch) for c in self.children]
        cap = batch.capacity
        valid = cols[0].validity
        for c in cols[1:]:
            valid = valid & c.validity
        lens = jnp.zeros(cap, jnp.int32)
        for c in cols:
            lens = lens + (c.offsets[1:] - c.offsets[:-1])
        lens = jnp.where(valid, lens, 0)
        new_offsets = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(lens).astype(jnp.int32)])
        from ..analysis import residency  # lazy: avoids import cycle
        with residency.declared_transfer(site="size_probe"):
            total = int(new_offsets[-1])
        out_bytes = bucket_capacity(max(1, total))
        out = jnp.zeros(out_bytes, jnp.uint8)
        # lay out piece k of each row after pieces 0..k-1
        piece_off = jnp.zeros(cap, jnp.int32)
        for c in cols:
            piece_lens = jnp.where(valid, c.offsets[1:] - c.offsets[:-1], 0)
            dst_start = new_offsets[:-1] + piece_off
            # place bytes of this piece
            piece_offsets = jnp.concatenate(
                [jnp.zeros(1, jnp.int32),
                 jnp.cumsum(piece_lens).astype(jnp.int32)])
            piece_buf = str_materialize_bytes(c.data, piece_offsets,
                                           c.offsets[:-1], out_bytes)
            # scatter piece bytes to dst positions
            j = jnp.arange(out_bytes, dtype=jnp.int32)
            src_row = jnp.clip(
                jnp.searchsorted(piece_offsets[1:], j, side="right"), 0,
                cap - 1)
            dst_idx = jnp.take(dst_start, src_row) + (
                j - jnp.take(piece_offsets[:-1], src_row))
            live = j < piece_offsets[-1]
            out = out.at[jnp.where(live, dst_idx, out_bytes - 1)].set(
                jnp.where(live, piece_buf, out[out_bytes - 1]))
            piece_off = piece_off + piece_lens
        return StringColumn(new_offsets, out, valid)


class StringTrim(Expression):
    side = "both"

    def __init__(self, child):
        self.children = [child]

    def with_children(self, c):
        return type(self)(c[0])

    def dtype(self):
        return T.STRING

    def columnar_eval(self, batch):
        col = _eval_string(self.children[0], batch)
        # count leading/trailing spaces per row on device
        data = col.data
        starts = col.offsets[:-1]
        lens = col.offsets[1:] - starts
        from ..analysis import residency  # lazy: avoids import cycle
        with residency.declared_transfer(site="strings_prep"):
            max_len_host = int(np.asarray(lens[:batch.num_rows]).max()) \
                if batch.num_rows else 0
        K = max(1, 1 << (max(max_len_host, 1) - 1).bit_length())
        k = jnp.arange(K, dtype=jnp.int32)
        idx = jnp.clip(starts[:, None] + k[None, :], 0, data.shape[0] - 1)
        byts = jnp.take(data, idx)
        inb = k[None, :] < lens[:, None]
        is_space = (byts == 32) & inb
        lead = jnp.argmin(jnp.where(is_space, 0, 1) +
                          jnp.where(inb, 0, 1), axis=1)
        # lead = count of leading spaces: first position that is not space
        not_space_inb = (~is_space) & inb
        any_ns = jnp.any(not_space_inb, axis=1)
        first_ns = jnp.argmax(not_space_inb, axis=1)
        last_ns = (K - 1) - jnp.argmax(not_space_inb[:, ::-1], axis=1)
        if type(self).side in ("both", "leading"):
            new_start_rel = jnp.where(any_ns, first_ns, lens)
        else:
            new_start_rel = jnp.zeros_like(lens)
        if type(self).side in ("both", "trailing"):
            new_end_rel = jnp.where(any_ns, last_ns + 1, lens)
            if type(self).side == "trailing":
                new_start_rel = jnp.zeros_like(lens)
                new_end_rel = jnp.where(any_ns, last_ns + 1, 0)
        else:
            new_end_rel = lens
        if type(self).side == "leading":
            new_end_rel = lens
        if type(self).side == "both":
            new_end_rel = jnp.where(any_ns, last_ns + 1, first_ns)
        new_lens = jnp.maximum(new_end_rel - new_start_rel, 0).astype(jnp.int32)
        new_lens = jnp.where(col.validity, new_lens, 0)
        src_starts = (starts + new_start_rel).astype(jnp.int32)
        from ..columnar.column import bucket_capacity
        from ..kernels.strings import str_materialize_bytes
        new_offsets = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(new_lens).astype(jnp.int32)])
        from ..analysis import residency  # lazy: avoids import cycle
        with residency.declared_transfer(site="size_probe"):
            total = int(new_offsets[-1])
        buf = str_materialize_bytes(col.data, new_offsets, src_starts,
                                 bucket_capacity(max(1, total)))
        return StringColumn(new_offsets, buf, col.validity,
                            max_bytes=col.max_bytes)


class StringTrimLeft(StringTrim):
    side = "leading"


class StringTrimRight(StringTrim):
    side = "trailing"


class _HostStringOp(Expression):
    """Base for string ops evaluated via host round-trip (the reference

    similarly keeps rare/irregular string ops off the fast path or gates
    them by conf; device byte kernels can replace these incrementally)."""

    def __init__(self, *children, **params):
        self.children = list(children)
        self.params = params

    def with_children(self, c):
        return type(self)(*c, **self.params)

    def dtype(self):
        return T.STRING

    def host_fn(self, *vals):
        raise NotImplementedError

    def columnar_eval(self, batch):
        n = batch.num_rows
        cols = [as_column(c.columnar_eval(batch), batch.capacity, n)
                for c in self.children]
        lists = [c.to_pylist(n) for c in cols]
        out = []
        for row in zip(*lists):
            if any(v is None for v in row):
                out.append(None)
            else:
                out.append(self.host_fn(*row))
        return StringColumn.from_pylist(
            out + [None] * (batch.capacity - n), capacity=batch.capacity)


class Replace(_HostStringOp):
    """replace(str, search, replace) (reference: GpuStringReplace)."""

    def host_fn(self, s, search, rep):
        return s.replace(search, rep) if search else s


class Reverse(Expression):
    """reverse(str) — device kernel: per-row byte reversal via index math.

    (Reverses code points; built from the same windowed-gather primitive
    as substring.)"""

    def __init__(self, child):
        self.children = [child]

    def with_children(self, c):
        return Reverse(c[0])

    def dtype(self):
        return T.STRING

    def columnar_eval(self, batch):
        # correct for ASCII via pure byte reversal; multi-byte code points
        # handled by host fallback when any non-ASCII byte present
        col = _eval_string(self.children[0], batch)
        import numpy as np
        from ..analysis import residency  # lazy: avoids import cycle
        with residency.declared_transfer(site="strings_prep"):
            has_mb = bool(np.asarray((col.data & 0x80) != 0).any())
        if has_mb:
            vals, valid = col.to_numpy(batch.num_rows)
            out = [v[::-1] if ok else None for v, ok in zip(vals, valid)]
            return StringColumn.from_pylist(
                out + [None] * (batch.capacity - batch.num_rows),
                capacity=batch.capacity)
        starts = col.offsets[:-1]
        ends = col.offsets[1:]
        B = col.data.shape[0]
        j = jnp.arange(B, dtype=jnp.int32)
        row = jnp.clip(jnp.searchsorted(col.offsets[1:], j, side="right"),
                       0, col.capacity - 1)
        src = jnp.clip(starts[row] + (ends[row] - 1 - j), 0, B - 1)
        return StringColumn(col.offsets, jnp.take(col.data, src),
                            col.validity, max_bytes=col.max_bytes)


class StringRepeat(_HostStringOp):
    def host_fn(self, s, n):
        return s * max(int(n), 0)


class Lpad(_HostStringOp):
    def host_fn(self, s, n, pad):
        n = int(n)
        if len(s) >= n:
            return s[:n]
        if not pad:
            return s
        fill = (pad * n)[: n - len(s)]
        return fill + s


class Rpad(_HostStringOp):
    def host_fn(self, s, n, pad):
        n = int(n)
        if len(s) >= n:
            return s[:n]
        if not pad:
            return s
        fill = (pad * n)[: n - len(s)]
        return s + fill


class InitCap(_HostStringOp):
    def host_fn(self, s):
        return " ".join(w[:1].upper() + w[1:].lower() if w else w
                        for w in s.split(" "))


class StringLocate(Expression):
    """instr/locate: 1-based position of substring, 0 if absent."""

    def __init__(self, substr: Expression, child: Expression):
        self.children = [substr, child]

    def with_children(self, c):
        return StringLocate(c[0], c[1])

    def dtype(self):
        return T.INT32

    def columnar_eval(self, batch):
        import numpy as np
        n = batch.num_rows
        sub = as_column(self.children[0].columnar_eval(batch),
                        batch.capacity, n)
        s = as_column(self.children[1].columnar_eval(batch),
                      batch.capacity, n)
        subs, sv = sub.to_numpy(n)
        vals, vv = s.to_numpy(n)
        out = np.zeros(batch.capacity, np.int32)
        ok = np.zeros(batch.capacity, bool)
        for i in range(n):
            if sv[i] and vv[i]:
                ok[i] = True
                out[i] = vals[i].find(subs[i]) + 1
        return Column(T.INT32, jnp.asarray(out), jnp.asarray(ok))


class ConcatWs(Expression):
    """concat_ws(sep, cols...): nulls skipped (unlike concat)."""

    def __init__(self, sep: str, *children):
        self.sep = sep
        self.children = list(children)

    def with_children(self, c):
        return ConcatWs(self.sep, *c)

    def dtype(self):
        return T.STRING

    def columnar_eval(self, batch):
        n = batch.num_rows
        cols = [as_column(c.columnar_eval(batch), batch.capacity, n)
                for c in self.children]
        lists = [c.to_pylist(n) for c in cols]
        out = []
        for row in zip(*lists) if lists else [()] * n:
            out.append(self.sep.join(str(v) for v in row if v is not None))
        return StringColumn.from_pylist(
            out + [None] * (batch.capacity - n), capacity=batch.capacity)


class RegexpReplace(_HostStringOp):
    """regexp_replace (host regex; reference gates regex similarly)."""

    def host_fn(self, s, pattern, rep):
        return re.sub(pattern, rep.replace("$", "\\\\"), s)


class RegexpExtract(Expression):
    def __init__(self, child, pattern: Expression, group: int = 1):
        self.children = [child, pattern]
        self.group = group

    def with_children(self, c):
        return RegexpExtract(c[0], c[1], self.group)

    def dtype(self):
        return T.STRING

    def columnar_eval(self, batch):
        pat = self.children[1]
        assert isinstance(pat, Literal)
        _note_host_regex(f"REGEXP_EXTRACT {pat.value!r}")
        rx = re.compile(str(pat.value))
        col = _eval_string(self.children[0], batch)
        vals, valid = col.to_numpy(batch.num_rows)
        out = []
        for i in range(batch.num_rows):
            if not valid[i]:
                out.append(None)
            else:
                m = rx.search(vals[i])
                out.append(m.group(self.group) if m and
                           self.group <= (m.lastindex or 0) else "")
        return StringColumn.from_pylist(
            out + [None] * (batch.capacity - batch.num_rows),
            capacity=batch.capacity)
