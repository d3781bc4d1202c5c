"""String expressions over fixed-width padded byte matrices.

Reference: sql-plugin/.../sql/rapids/stringFunctions.scala (1,983 LoC —
GpuSubstring, GpuUpper/Lower, GpuConcat, GpuStringTrim, GpuContains,
GpuStartsWith/EndsWith, GpuLike, GpuStringRepeat, GpuLength…). cudf gets
offsets+chars columns; here every string column is ``uint8[rows, max_len]``
plus a length vector (types.py rationale), so the kernels below are pure
rectangular VPU ops:

- per-row byte COMPACTION (the substring/trim/replace workhorse) is a
  cumsum-scatter along the byte axis — no Python, no dynamic shapes;
- SEARCH (contains/starts/ends/locate/replace) is a shifted-window
  all-equal reduction, vectorized over every (row, shift) pair at once.

Unicode: lengths/substr index by CODEPOINT (UTF-8 lead-byte cumsum), like
Spark. upper/lower map ASCII bytewise plus SIMPLE (single-char,
length-preserving) case tables for the 2-byte (U+0080-U+07FF) and 3-byte
(U+0800-U+FFFF) UTF-8 ranges — Latin/Greek/Cyrillic through Georgian,
Cherokee, full-width Latin. Length-changing mappings (ß→SS), cross-width
mappings and 4-byte scripts pass through unchanged; that residue is why
Upper/Lower stay default-incompat in the planner (the reference gates
locale-sensitive case the same way).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..batch import ColumnarBatch, DeviceColumn
from ..types import SqlType, TypeKind
from .base import EvalContext, Expression, and_validity


def _is_lead(data: jnp.ndarray) -> jnp.ndarray:
    """True for UTF-8 lead bytes (not 10xxxxxx continuations)."""
    return (data & 0xC0) != 0x80


def _char_count(col: DeviceColumn) -> jnp.ndarray:
    ml = col.data.shape[1]
    in_str = jnp.arange(ml)[None, :] < col.lengths[:, None]
    return jnp.sum((_is_lead(col.data) & in_str).astype(jnp.int32), axis=1)


def _compact_bytes(data: jnp.ndarray, keep: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Left-pack kept bytes per row; returns (packed, new_lengths)."""
    n, ml = data.shape
    pos = jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1
    flat_target = jnp.where(keep,
                            jnp.arange(n)[:, None] * ml + pos,
                            n * ml)
    out = jnp.zeros(n * ml + 1, data.dtype).at[flat_target.reshape(-1)].set(
        data.reshape(-1), mode="drop")[: n * ml].reshape(n, ml)
    return out, jnp.sum(keep.astype(jnp.int32), axis=1)


def _string_column(data, lengths, validity, max_len: int) -> DeviceColumn:
    # zero bytes past each row's length (canonical padding)
    mask = jnp.arange(data.shape[1])[None, :] < lengths[:, None]
    data = jnp.where(mask & validity[:, None], data, 0)
    lengths = jnp.where(validity, lengths, 0)
    return DeviceColumn(data, validity, lengths, T.string(max_len))


@dataclass(frozen=True, eq=False)
class Length(Expression):
    """char_length: CODEPOINTS, not bytes (Spark length)."""

    child: Expression

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return Length(c[0])

    @property
    def dtype(self):
        return T.INT32

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        from .base import numeric_column
        return numeric_column(_char_count(c), c.validity, T.INT32)


def _case_tables():
    """Single-char case maps for the 2-byte UTF-8 range (U+0080-U+07FF:
    Latin-1 Supplement, Latin Extended, Greek, Cyrillic, ...): codepoint ->
    codepoint, identity where the mapping changes char count or leaves the
    2-byte range (those rows are why Upper/Lower are default-incompat)."""
    import numpy as np
    up = np.arange(0x800, dtype=np.int32)
    lo = np.arange(0x800, dtype=np.int32)
    for cp in range(0x80, 0x800):
        u = chr(cp).upper()
        if len(u) == 1 and 0x80 <= ord(u) < 0x800:
            up[cp] = ord(u)
        l = chr(cp).lower()
        if len(l) == 1 and 0x80 <= ord(l) < 0x800:
            lo[cp] = ord(l)
    return up, lo


_UPPER_2B, _LOWER_2B = _case_tables()


def _case_tables_3b():
    """Single-char case maps for the 3-byte UTF-8 range (U+0800-U+FFFF:
    Georgian, Cherokee, full-width Latin, Greek Extended, ...): identity
    where the mapping changes char count or leaves the 3-byte range."""
    import numpy as np
    up = np.arange(0x10000, dtype=np.int32)
    lo = np.arange(0x10000, dtype=np.int32)
    for cp in range(0x800, 0x10000):
        ch = chr(cp)
        u = ch.upper()
        if len(u) == 1 and 0x800 <= ord(u) < 0x10000:
            up[cp] = ord(u)
        l = ch.lower()
        if len(l) == 1 and 0x800 <= ord(l) < 0x10000:
            lo[cp] = ord(l)
    return up, lo


_UPPER_3B, _LOWER_3B = _case_tables_3b()


@dataclass(frozen=True, eq=False)
class Upper(Expression):
    """upper/lower: ASCII bytewise plus SIMPLE case mapping for every
    2-byte codepoint whose counterpart is also 2-byte (Latin-1/Extended,
    Greek, Cyrillic) and every 3-byte codepoint whose counterpart is also
    3-byte (Georgian, Cherokee, full-width Latin, Greek Extended).
    Length-changing mappings (ß→SS), cross-width mappings and 4-byte
    scripts pass through — the rule is default-incompat for that residue
    (reference gates locale-sensitive case the same way)."""

    child: Expression
    _upper = True

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return type(self)(c[0])

    @property
    def dtype(self):
        return self.child.dtype

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        d = c.data
        if self._upper:
            is_lo = (d >= ord("a")) & (d <= ord("z"))
            out = jnp.where(is_lo, d - 32, d)
            table = jnp.asarray(_UPPER_2B)
        else:
            is_up = (d >= ord("A")) & (d <= ord("Z"))
            out = jnp.where(is_up, d + 32, d)
            table = jnp.asarray(_LOWER_2B)
        def ahead(a, k):
            """a shifted left by k columns (peek at byte position +k)."""
            return jnp.concatenate(
                [a[:, k:], jnp.zeros_like(a[:, :k])], axis=1)

        def behind(a, k):
            """a shifted right by k columns (value from position -k)."""
            return jnp.concatenate(
                [jnp.zeros_like(a[:, :k]), a[:, :-k]], axis=1)

        def cont(b):
            return (b >= 0x80) & (b < 0xC0)

        # 2-byte sequences: lead 0xC2-0xDF followed by a continuation
        nxt = ahead(d, 1)
        lead2 = (d >= 0xC2) & (d <= 0xDF) & cont(nxt)
        cp = ((d.astype(jnp.int32) & 0x1F) << 6) \
            | (nxt.astype(jnp.int32) & 0x3F)
        mapped = jnp.take(table, jnp.clip(cp, 0, 0x7FF))
        bytes2 = [(0xC0 | (mapped >> 6)).astype(d.dtype),
                  (0x80 | (mapped & 0x3F)).astype(d.dtype)]
        # 3-byte sequences (U+0800-U+FFFF: Georgian, Cherokee, full-width
        # Latin, Greek Extended, ...): lead 0xE0-0xEF + two continuations
        table3 = jnp.asarray(_UPPER_3B if self._upper else _LOWER_3B)
        n2 = ahead(d, 2)
        lead3 = (d >= 0xE0) & (d <= 0xEF) & cont(nxt) & cont(n2)
        cp3 = ((d.astype(jnp.int32) & 0x0F) << 12) \
            | ((nxt.astype(jnp.int32) & 0x3F) << 6) \
            | (n2.astype(jnp.int32) & 0x3F)
        m3 = jnp.take(table3, jnp.clip(cp3, 0, 0xFFFF))
        bytes3 = [(0xE0 | (m3 >> 12)).astype(d.dtype),
                  (0x80 | ((m3 >> 6) & 0x3F)).astype(d.dtype),
                  (0x80 | (m3 & 0x3F)).astype(d.dtype)]
        # write each sequence byte at its position: byte k of a sequence
        # whose LEAD sat k columns back
        for lead, seq in ((lead2, bytes2), (lead3, bytes3)):
            out = jnp.where(lead, seq[0], out)
            for k in range(1, len(seq)):
                out = jnp.where(behind(lead, k), behind(seq[k], k), out)
        return DeviceColumn(out, c.validity, c.lengths, c.dtype)


class Lower(Upper):
    _upper = False


@dataclass(frozen=True, eq=False)
class Substring(Expression):
    """substring(str, pos, len): 1-based, negative pos counts from the end,
    pos=0 treated as 1 (Spark). Character-indexed."""

    child: Expression
    pos: Expression
    length: Optional[Expression] = None

    @property
    def children(self):
        return (self.child, self.pos) + (
            (self.length,) if self.length is not None else ())

    def with_children(self, c):
        return Substring(c[0], c[1], c[2] if len(c) > 2 else None)

    @property
    def dtype(self):
        return self.child.dtype

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        p = self.pos.eval(batch, ctx)
        parts = [c, p]
        if self.length is not None:
            ln = self.length.eval(batch, ctx)
            parts.append(ln)
            want = ln.data.astype(jnp.int32)
        else:
            want = jnp.full(c.capacity, 1 << 30, jnp.int32)
        validity = and_validity(parts)
        nchars = _char_count(c)
        pos = p.data.astype(jnp.int32)
        start = jnp.where(pos > 0, pos - 1,
                          jnp.where(pos < 0, nchars + pos, 0))
        start = jnp.maximum(start, jnp.where(pos < 0, 0, start))
        start = jnp.where((pos < 0) & (nchars + pos < 0), nchars, start)
        end = start + jnp.maximum(want, 0)
        ml = c.data.shape[1]
        in_str = jnp.arange(ml)[None, :] < c.lengths[:, None]
        lead = _is_lead(c.data) & in_str
        # char ordinal of each byte (0-based, continuation bytes inherit)
        char_ix = jnp.cumsum(lead.astype(jnp.int32), axis=1) - 1
        keep = in_str & (char_ix >= start[:, None]) & (char_ix < end[:, None])
        data, lengths = _compact_bytes(c.data, keep)
        return _string_column(data, lengths, validity, self.dtype.max_len)


@dataclass(frozen=True, eq=False)
class Concat(Expression):
    """concat(s1, s2, ...): null if ANY input is null (Spark concat)."""

    exprs: Tuple[Expression, ...]

    @property
    def children(self):
        return self.exprs

    def with_children(self, c):
        return Concat(tuple(c))

    @property
    def dtype(self):
        total = sum(e.dtype.max_len for e in self.exprs)
        return T.string(max(total, 1))

    def eval(self, batch, ctx=EvalContext()):
        cols = [e.eval(batch, ctx) for e in self.exprs]
        validity = and_validity(cols)
        out_ml = self.dtype.max_len
        n = batch.capacity
        out = jnp.zeros((n, out_ml), jnp.uint8)
        offset = jnp.zeros(n, jnp.int32)
        flat = jnp.zeros(n * out_ml + 1, jnp.uint8)
        for c in cols:
            ml = c.data.shape[1]
            in_str = jnp.arange(ml)[None, :] < c.lengths[:, None]
            target = jnp.where(in_str,
                               jnp.arange(n)[:, None] * out_ml
                               + offset[:, None] + jnp.arange(ml)[None, :],
                               n * out_ml)
            flat = flat.at[target.reshape(-1)].set(c.data.reshape(-1),
                                                   mode="drop")
            offset = offset + c.lengths
        out = flat[: n * out_ml].reshape(n, out_ml)
        return _string_column(out, jnp.minimum(offset, out_ml), validity,
                              out_ml)


#: Pallas substring kernel cutover: below this pattern length XLA's rolled
#: compares win; above it the single-VMEM-pass kernel does (measured
#: through the old plug-in on v5e: k=16 XLA 19 ms vs kernel ~15 ms at 4M x 64B; gap grows with k)
_PALLAS_SEARCH_MIN_K = 12


def _window_match(data: jnp.ndarray, lengths: jnp.ndarray,
                  pat: bytes) -> jnp.ndarray:
    """match[row, s] = pattern equals data[row, s:s+k] (k = len(pat))."""
    n, ml = data.shape
    k = len(pat)
    if k == 0:
        return jnp.arange(ml)[None, :] <= lengths[:, None]
    if k > ml:
        return jnp.zeros((n, ml), bool)
    if k >= _PALLAS_SEARCH_MIN_K:
        import jax as _jax
        from ..kernels.string_search import pallas_window_match, supports
        if supports(n, ml, pat) and \
                _jax.default_backend() not in ("cpu",):
            return pallas_window_match(data, lengths, pat)
    pat_a = jnp.asarray(bytearray(pat), jnp.uint8)
    m = jnp.ones((n, ml), bool)
    for j in range(k):
        shifted = jnp.roll(data, -j, axis=1)
        # positions where s+j < ml hold data[s+j]; beyond wraps — mask below
        m = m & (shifted == pat_a[j])
    valid_start = jnp.arange(ml)[None, :] + k <= lengths[:, None]
    return m & valid_start


@dataclass(frozen=True, eq=False)
class StringPredicate(Expression):
    """contains / startswith / endswith with a LITERAL pattern (the
    reference requires literal right-hand sides too — GpuContains)."""

    child: Expression
    pattern: Expression        # must be a Literal string
    op: str = "contains"       # contains | startswith | endswith

    @property
    def children(self):
        return (self.child, self.pattern)

    def with_children(self, c):
        return StringPredicate(c[0], c[1], self.op)

    @property
    def dtype(self):
        return T.BOOLEAN

    def _pat(self) -> bytes:
        from .base import Literal
        assert isinstance(self.pattern, Literal), \
            "string predicate pattern must be a literal"
        return str(self.pattern.value).encode("utf-8")

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        p = self.pattern.eval(batch, ctx)
        validity = c.validity & p.validity
        pat = self._pat()
        k = len(pat)
        m = _window_match(c.data, c.lengths, pat)
        if self.op == "contains":
            r = jnp.any(m, axis=1) | (k == 0)
        elif self.op == "startswith":
            r = (m[:, 0] | (k == 0)) & (c.lengths >= k)
        else:
            idx = jnp.clip(c.lengths - k, 0, c.data.shape[1] - 1)
            r = (jnp.take_along_axis(m, idx[:, None], axis=1)[:, 0]
                 | (k == 0)) & (c.lengths >= k)
        from .base import numeric_column
        return numeric_column(r, validity, T.BOOLEAN)


@dataclass(frozen=True, eq=False)
class StringLocate(Expression):
    """instr/locate: 1-based position of first occurrence, 0 if absent."""

    child: Expression
    pattern: Expression

    @property
    def children(self):
        return (self.child, self.pattern)

    def with_children(self, c):
        return StringLocate(c[0], c[1])

    @property
    def dtype(self):
        return T.INT32

    def eval(self, batch, ctx=EvalContext()):
        from .base import Literal, numeric_column
        c = self.child.eval(batch, ctx)
        p = self.pattern.eval(batch, ctx)
        assert isinstance(self.pattern, Literal)
        pat = str(self.pattern.value).encode("utf-8")
        m = _window_match(c.data, c.lengths, pat)
        ml = c.data.shape[1]
        first = jnp.argmax(m, axis=1)
        found = jnp.any(m, axis=1)
        # byte position -> char position (count leads before it) + 1
        lead = _is_lead(c.data)
        char_before = jnp.cumsum(lead.astype(jnp.int32), axis=1)
        pos = jnp.take_along_axis(char_before, first[:, None], axis=1)[:, 0]
        r = jnp.where(found, pos, 0)
        r = jnp.where(jnp.asarray(len(pat) == 0), 1, r)
        return numeric_column(r.astype(jnp.int32),
                              c.validity & p.validity, T.INT32)


@dataclass(frozen=True, eq=False)
class StringTrim(Expression):
    """trim/ltrim/rtrim of ASCII spaces (Spark default trim set)."""

    child: Expression
    side: str = "both"    # both | leading | trailing

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return StringTrim(c[0], self.side)

    @property
    def dtype(self):
        return self.child.dtype

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        ml = c.data.shape[1]
        in_str = jnp.arange(ml)[None, :] < c.lengths[:, None]
        is_space = (c.data == 32) & in_str
        nonspace = in_str & ~is_space
        any_ns = jnp.any(nonspace, axis=1)
        first_ns = jnp.argmax(nonspace, axis=1)
        last_ns = ml - 1 - jnp.argmax(nonspace[:, ::-1], axis=1)
        lo = jnp.where(any_ns, first_ns, 0) if self.side != "trailing" \
            else jnp.zeros(batch.capacity, jnp.int32)
        hi = jnp.where(any_ns, last_ns + 1, 0) if self.side != "leading" \
            else c.lengths
        hi = jnp.where(any_ns, hi, 0) if self.side == "leading" else hi
        keep = in_str & (jnp.arange(ml)[None, :] >= lo[:, None]) & \
            (jnp.arange(ml)[None, :] < hi[:, None])
        data, lengths = _compact_bytes(c.data, keep)
        return _string_column(data, lengths, c.validity, self.dtype.max_len)


@dataclass(frozen=True, eq=False)
class StringPad(Expression):
    """lpad/rpad(str, len, pad): CHARACTER-counted (ASCII pad assumed)."""

    child: Expression
    target_len: Expression
    pad: Expression
    left: bool = True

    @property
    def children(self):
        return (self.child, self.target_len, self.pad)

    def with_children(self, c):
        return StringPad(c[0], c[1], c[2], self.left)

    @property
    def dtype(self):
        return T.string(max(self.child.dtype.max_len, 64))

    def eval(self, batch, ctx=EvalContext()):
        from .base import Literal
        c = self.child.eval(batch, ctx)
        tl = self.target_len.eval(batch, ctx)
        pd = self.pad.eval(batch, ctx)
        validity = and_validity([c, tl, pd])
        assert isinstance(self.pad, Literal)
        pad_bytes = str(self.pad.value).encode("utf-8")
        out_ml = self.dtype.max_len
        n = batch.capacity
        want = jnp.clip(tl.data.astype(jnp.int32), 0, out_ml)
        cur = _char_count(c)  # == byte count for ASCII content
        deficit = jnp.maximum(want - cur, 0)
        deficit = jnp.where(jnp.asarray(len(pad_bytes) == 0), 0, deficit)
        # truncation case: want < cur -> keep first `want` chars
        ml = c.data.shape[1]
        in_str = jnp.arange(ml)[None, :] < c.lengths[:, None]
        lead = _is_lead(c.data) & in_str
        char_ix = jnp.cumsum(lead.astype(jnp.int32), axis=1) - 1
        keep = in_str & (char_ix < want[:, None])
        body, body_len = _compact_bytes(c.data, keep)
        if len(pad_bytes) == 0:
            pad_row = jnp.zeros(out_ml, jnp.uint8)
        else:
            reps = -(-out_ml // len(pad_bytes))
            pad_row = jnp.asarray(
                bytearray((pad_bytes * reps)[:out_ml]), jnp.uint8)
        total = jnp.minimum(body_len + deficit, out_ml)
        j = jnp.arange(out_ml)[None, :]
        wide_body = jnp.pad(body, ((0, 0), (0, max(out_ml - ml, 0))))
        wide_body = wide_body[:, :out_ml]
        pad_mat = jnp.broadcast_to(pad_row, (n, out_ml))
        if self.left:
            # pad occupies [0, deficit), body shifts right
            from_body = j >= deficit[:, None]
            body_g = jnp.take_along_axis(
                wide_body, jnp.clip(j - deficit[:, None], 0, out_ml - 1),
                axis=1)
            out = jnp.where(from_body, body_g, pad_mat)
        else:
            in_body = j < body_len[:, None]
            pad_g = jnp.take_along_axis(
                pad_mat, jnp.clip(j - body_len[:, None], 0, out_ml - 1),
                axis=1)
            out = jnp.where(in_body, wide_body, pad_g)
        return _string_column(out, total, validity, out_ml)


@dataclass(frozen=True, eq=False)
class StringRepeat(Expression):
    child: Expression
    times: Expression

    @property
    def children(self):
        return (self.child, self.times)

    def with_children(self, c):
        return StringRepeat(c[0], c[1])

    @property
    def dtype(self):
        return T.string(max(self.child.dtype.max_len * 4, 64))

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        t = self.times.eval(batch, ctx)
        validity = c.validity & t.validity
        out_ml = self.dtype.max_len
        n = batch.capacity
        reps = jnp.clip(t.data.astype(jnp.int32), 0, out_ml)
        total = jnp.minimum(c.lengths * reps, out_ml)
        j = jnp.arange(out_ml)[None, :]
        safe_len = jnp.maximum(c.lengths, 1)[:, None]
        src = (j % safe_len).astype(jnp.int32)
        ml = c.data.shape[1]
        g = jnp.take_along_axis(
            jnp.pad(c.data, ((0, 0), (0, max(out_ml - ml, 0)))),
            jnp.clip(src, 0, out_ml - 1), axis=1)
        out = jnp.where(j < total[:, None], g, 0)
        return _string_column(out, total, validity, out_ml)


@dataclass(frozen=True, eq=False)
class StringReplace(Expression):
    """replace(str, search, replace) with LITERAL search/replace
    (reference: GpuStringReplace has the same literal restriction)."""

    child: Expression
    search: Expression
    replacement: Expression

    @property
    def children(self):
        return (self.child, self.search, self.replacement)

    def with_children(self, c):
        return StringReplace(c[0], c[1], c[2])

    @property
    def dtype(self):
        return T.string(max(self.child.dtype.max_len * 2, 64))

    def eval(self, batch, ctx=EvalContext()):
        from .base import Literal
        c = self.child.eval(batch, ctx)
        assert isinstance(self.search, Literal) and \
            isinstance(self.replacement, Literal)
        pat = str(self.search.value).encode("utf-8")
        rep = str(self.replacement.value).encode("utf-8")
        out_ml = self.dtype.max_len
        n, ml = c.data.shape
        if len(pat) == 0:
            padded = jnp.pad(c.data, ((0, 0), (0, max(out_ml - ml, 0))))
            return _string_column(padded[:, :out_ml],
                                  jnp.minimum(c.lengths, out_ml),
                                  c.validity, out_ml)
        m = _window_match(c.data, c.lengths, pat)
        k = len(pat)
        # greedy left-to-right non-overlapping matches: a match at s is real
        # iff no real match covers s. scan over byte positions.
        def step(carry, s_col):
            blocked_until, _ = carry
            s, matched = s_col
            real = matched & (s.astype(jnp.int32) >= blocked_until)
            blocked_until = jnp.where(
                real, (s + k).astype(jnp.int32), blocked_until)
            return (blocked_until, real), real

        ss = jnp.arange(ml, dtype=jnp.int32)
        (_, _), reals = jax.lax.scan(
            step, (jnp.zeros(n, jnp.int32), jnp.zeros(n, bool)),
            (ss, m.T))
        real = reals.T   # [n, ml] real match starts
        # each byte is either copied (not inside any real match) or part of
        # a match start (emits rep bytes)
        inside = jnp.zeros((n, ml), bool)
        cover = jnp.cumsum(real.astype(jnp.int32), axis=1) - \
            jnp.cumsum(jnp.pad(real, ((0, 0), (k, 0)))[:, :ml].astype(
                jnp.int32), axis=1)
        inside = cover > 0
        in_str = jnp.arange(ml)[None, :] < c.lengths[:, None]
        # output length per row
        n_matches = jnp.sum(real.astype(jnp.int32), axis=1)
        out_len = jnp.minimum(c.lengths + n_matches * (len(rep) - k), out_ml)
        # emit: for each byte position, its output offset
        emit_copy = in_str & ~inside
        unit = emit_copy.astype(jnp.int32) + real.astype(jnp.int32) * len(rep)
        offs = jnp.cumsum(unit, axis=1) - unit
        out = jnp.zeros(n * out_ml + 1, jnp.uint8)
        # copied bytes
        tgt = jnp.where(emit_copy & (offs < out_ml),
                        jnp.arange(n)[:, None] * out_ml + offs, n * out_ml)
        out = out.at[tgt.reshape(-1)].set(c.data.reshape(-1), mode="drop")
        # replacement bytes
        rep_a = jnp.asarray(bytearray(rep), jnp.uint8) if rep else None
        for j in range(len(rep)):
            tgt_j = jnp.where(real & (offs + j < out_ml),
                              jnp.arange(n)[:, None] * out_ml + offs + j,
                              n * out_ml)
            out = out.at[tgt_j.reshape(-1)].set(rep_a[j], mode="drop")
        out = out[: n * out_ml].reshape(n, out_ml)
        return _string_column(out, out_len, c.validity, out_ml)


def upper(e):
    return Upper(e)


def lower(e):
    return Lower(e)


def length(e):
    return Length(e)


def substring(e, pos, ln=None):
    from .base import lit_if_needed
    return Substring(e, lit_if_needed(pos),
                     lit_if_needed(ln) if ln is not None else None)


def concat(*es):
    return Concat(tuple(es))


def contains(e, pat):
    from .base import lit_if_needed
    return StringPredicate(e, lit_if_needed(pat), "contains")


def startswith(e, pat):
    from .base import lit_if_needed
    return StringPredicate(e, lit_if_needed(pat), "startswith")


def endswith(e, pat):
    from .base import lit_if_needed
    return StringPredicate(e, lit_if_needed(pat), "endswith")


@dataclass(frozen=True, eq=False)
class Translate(Expression):
    """translate(str, from, to): per-byte substitution via one 256-entry
    lookup table built at bind time (the cudf translate table, but as a
    gather instead of per-char dispatch). Bytes mapped to "delete" (from
    chars beyond len(to)) are compacted out. ASCII from/to only — a
    non-ASCII mapping would need char-level re-encoding → CPU fallback."""

    child: Expression = None
    from_str: str = ""
    to_str: str = ""

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return Translate(c[0], self.from_str, self.to_str)

    @property
    def dtype(self):
        return self.child.dtype

    def device_unsupported_reason(self):
        try:
            self.from_str.encode("ascii")
            self.to_str.encode("ascii")
        except UnicodeEncodeError:
            return "translate: non-ASCII mapping needs char re-encoding"
        return None

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        table = np.arange(256, dtype=np.uint8)
        delete = np.zeros(256, bool)
        seen = set()
        for i, ch in enumerate(self.from_str):
            b = ord(ch)
            if b in seen:       # Spark: first occurrence wins
                continue
            seen.add(b)
            if i < len(self.to_str):
                table[b] = ord(self.to_str[i])
            else:
                delete[b] = True
        mapped = jnp.asarray(table)[c.data.astype(jnp.int32)]
        in_str = jnp.arange(c.data.shape[1])[None, :] < c.lengths[:, None]
        keep = in_str & ~jnp.asarray(delete)[c.data.astype(jnp.int32)]
        out, lengths = _compact_bytes(mapped, keep)
        return _string_column(out, lengths, c.validity, c.dtype.max_len)


@dataclass(frozen=True, eq=False)
class InitCap(Expression):
    """initcap(str): first letter of each whitespace-separated word upper,
    the rest lower. ASCII case mapping (the Upper/Lower policy)."""

    child: Expression = None

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return InitCap(c[0])

    @property
    def dtype(self):
        return self.child.dtype

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        d = c.data
        is_up = (d >= ord("A")) & (d <= ord("Z"))
        lowered = jnp.where(is_up, d + 32, d)
        # word start = position 0 or previous byte is a space
        prev_space = jnp.concatenate(
            [jnp.ones((d.shape[0], 1), bool),
             d[:, :-1] == ord(" ")], axis=1)
        is_lo = (lowered >= ord("a")) & (lowered <= ord("z"))
        out = jnp.where(prev_space & is_lo, lowered - 32, lowered)
        return DeviceColumn(out, c.validity, c.lengths, c.dtype)


@dataclass(frozen=True, eq=False)
class FormatNumber(Expression):
    """format_number(x, d): fixed decimals + thousands separators.
    Digit extraction is pure integer math on the device: round to 10^d,
    emit digits most-significant-first, insert ',' every 3 integer digits.
    Doubles round HALF_UP on the scaled value like Spark."""

    child: Expression = None
    decimals: int = 2

    _MAX_DIGITS = 19     # int64 decimal digits

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return FormatNumber(c[0], self.decimals)

    @property
    def dtype(self):
        # digits + separators + sign + point + decimals
        n = self._MAX_DIGITS
        return T.string(n + (n - 1) // 3 + 2 + max(self.decimals, 0))

    def device_unsupported_reason(self):
        if self.decimals < 0:
            return "format_number: negative d"
        if self.decimals > 9:
            return "format_number: d > 9 overflows the int64 scaling"
        from ..types import TypeKind
        if self.child.resolved and \
                self.child.dtype.kind in (TypeKind.FLOAT32,
                                          TypeKind.FLOAT64):
            return ("format_number over floats: exact HALF_UP on the "
                    "decimal expansion needs arbitrary precision")
        return None

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        d = self.decimals
        kind = self.child.dtype.kind
        x = c.data
        from ..types import TypeKind
        # compute (integer magnitude, fraction value scaled to d digits)
        # WITHOUT up-scaling the whole value — x * 10**d overflows int64
        # for large longs
        if kind is TypeKind.DECIMAL:
            scale = self.child.dtype.scale
            v = x.astype(jnp.int64)
            if scale > d:
                # rescale to d decimals, HALF_EVEN (DecimalFormat default);
                # floor division toward -inf keeps r in [0, div)
                div = 10 ** (scale - d)
                q = v // div
                r = v - q * div
                up = (2 * r > div) | ((2 * r == div) & (q % 2 != 0))
                v = q + up.astype(jnp.int64)
                mag = jnp.abs(v)
                int_mag = mag // (10 ** d)
                frac_val = mag % (10 ** d) if d else jnp.zeros_like(mag)
            else:
                mag = jnp.abs(v)
                int_mag = mag // (10 ** scale) if scale else mag
                frac_val = (mag % (10 ** scale)) * (10 ** (d - scale)) \
                    if scale else jnp.zeros_like(mag)
        else:   # integral kinds: fraction digits are exactly zero
            int_mag = jnp.abs(x.astype(jnp.int64))
            frac_val = jnp.zeros_like(int_mag)

        neg = x < 0      # original sign: -0.004 formats as "-0.00" (Java)
        # integer digits, most significant first, over the fixed budget.
        # uint64 digit math: |INT64_MIN| only exists unsigned
        nd = self._MAX_DIGITS
        powers = jnp.asarray([10 ** i for i in range(nd - 1, -1, -1)],
                             jnp.uint64)
        int_digits_mat = ((int_mag.astype(jnp.uint64)[:, None] //
                           powers[None, :]) % 10).astype(jnp.int64)
        n_int = jnp.maximum(
            nd - jnp.argmax(int_digits_mat > 0, axis=1)
            - (jnp.max(int_digits_mat, axis=1) == 0) * (nd - 1),
            1)
        # build output right-to-left into a fixed buffer
        out_ml = self.dtype.max_len
        n = x.shape[0]
        buf = jnp.zeros((n, out_ml), jnp.uint8)
        # layout: [sign][int digits with commas][.][frac digits]
        n_commas = (n_int - 1) // 3
        total = neg.astype(jnp.int32) + n_int + n_commas + \
            (1 + d if d > 0 else 0)
        # position helpers: write each character class via scatter
        r_idx = jnp.arange(n)[:, None]
        # fraction digits: positions total-d .. total-1
        if d > 0:
            fpowers = jnp.asarray([10 ** i for i in range(d - 1, -1, -1)],
                                  jnp.int64)
            frac = (frac_val[:, None] // fpowers[None, :]) % 10
            fpos = (total - d)[:, None] + jnp.arange(d)[None, :]
            buf = buf.at[r_idx, fpos].set(
                (frac + ord("0")).astype(jnp.uint8), mode="drop")
            dot = (total - d - 1)[:, None]
            buf = buf.at[r_idx, dot].set(jnp.uint8(ord(".")), mode="drop")
        # integer digits with commas, right to left
        int_end = total - (1 + d if d > 0 else 0)   # one past last int char
        for k in range(nd):
            # k-th integer digit from the right
            dig = int_digits_mat[:, nd - 1 - k]
            # its output position: k digits + commas passed so far
            pos = int_end - 1 - k - (k // 3) - \
                jnp.zeros_like(int_end)
            write = k < n_int
            buf = buf.at[r_idx, jnp.where(write, pos, out_ml)[:, None]].set(
                (dig + ord("0")).astype(jnp.uint8)[:, None], mode="drop")
            if (k + 1) % 3 == 0:
                cpos = pos - 1
                cwrite = (k + 1) < n_int
                buf = buf.at[r_idx,
                             jnp.where(cwrite, cpos, out_ml)[:, None]].set(
                    jnp.uint8(ord(",")), mode="drop")
        sign_pos = jnp.where(neg, 0, out_ml)
        buf = buf.at[r_idx, sign_pos[:, None]].set(jnp.uint8(ord("-")),
                                                   mode="drop")
        return _string_column(buf, total, c.validity, out_ml)


# ---------------------------------------------------------------------------
# Codepoint decode/encode (UTF-8 unit <-> int32 codepoint matrices) — the
# foundation for character-order ops (reverse/levenshtein/ascii). cudf keeps
# a character-index structure; here both directions are rectangular gathers/
# scatters over the padded byte matrix.
# ---------------------------------------------------------------------------

def _decode_cp(b0, b1, b2, b3):
    """UTF-8 unit bytes -> codepoint (shared by every decode site)."""
    return jnp.where(
        b0 < 0x80, b0,
        jnp.where(b0 < 0xE0, ((b0 & 0x1F) << 6) | (b1 & 0x3F),
                  jnp.where(b0 < 0xF0,
                            ((b0 & 0x0F) << 12) | ((b1 & 0x3F) << 6)
                            | (b2 & 0x3F),
                            ((b0 & 0x07) << 18) | ((b1 & 0x3F) << 12)
                            | ((b2 & 0x3F) << 6) | (b3 & 0x3F))))


def _codepoints(col: DeviceColumn) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(codepoints [n, ml] int32 left-packed, char counts [n]). Slots past
    a row's character count are 0."""
    n, ml = col.data.shape
    pos = jnp.arange(ml, dtype=jnp.int32)[None, :]
    in_str = pos < col.lengths[:, None]
    lead = _is_lead(col.data) & in_str
    starts, nchars = _compact_bytes(
        jnp.broadcast_to(pos, (n, ml)), lead)

    def byte_at(off):
        idx = jnp.clip(starts + off, 0, ml - 1)
        b = jnp.take_along_axis(col.data, idx, axis=1).astype(jnp.int32)
        ok = (starts + off) < col.lengths[:, None]
        return jnp.where(ok, b, 0)

    b0, b1, b2, b3 = byte_at(0), byte_at(1), byte_at(2), byte_at(3)
    cp = _decode_cp(b0, b1, b2, b3)
    char_live = pos < nchars[:, None]
    return jnp.where(char_live, cp, 0), nchars


def _encode_utf8(cps: jnp.ndarray, counts: jnp.ndarray, out_ml: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Encode left-packed codepoints back to a padded UTF-8 byte matrix;
    returns (bytes [n, out_ml], byte lengths [n])."""
    n, ml = cps.shape
    pos = jnp.arange(ml, dtype=jnp.int32)[None, :]
    live = pos < counts[:, None]
    ulen = jnp.where(cps < 0x80, 1,
                     jnp.where(cps < 0x800, 2,
                               jnp.where(cps < 0x10000, 3, 4)))
    ulen = jnp.where(live, ulen, 0)
    offs = jnp.cumsum(ulen, axis=1) - ulen          # exclusive prefix
    lengths = jnp.sum(ulen, axis=1).astype(jnp.int32)

    def enc_byte(k):
        one = jnp.where(k == 0, cps, 0)
        two = jnp.where(k == 0, 0xC0 | (cps >> 6),
                        0x80 | (cps & 0x3F))
        three = jnp.where(k == 0, 0xE0 | (cps >> 12),
                          jnp.where(k == 1, 0x80 | ((cps >> 6) & 0x3F),
                                    0x80 | (cps & 0x3F)))
        four = jnp.where(k == 0, 0xF0 | (cps >> 18),
                         jnp.where(k == 1, 0x80 | ((cps >> 12) & 0x3F),
                                   jnp.where(k == 2,
                                             0x80 | ((cps >> 6) & 0x3F),
                                             0x80 | (cps & 0x3F))))
        return jnp.where(ulen == 1, one,
                         jnp.where(ulen == 2, two,
                                   jnp.where(ulen == 3, three, four)))

    out = jnp.zeros(n * out_ml + 1, jnp.uint8)
    row_base = jnp.arange(n, dtype=jnp.int32)[:, None] * out_ml
    for k in range(4):
        val = enc_byte(k).astype(jnp.uint8)
        write = live & (k < ulen)
        tgt = jnp.where(write, row_base + offs + k, n * out_ml)
        out = out.at[tgt.reshape(-1)].set(
            val.reshape(-1), mode="drop")
    return out[:n * out_ml].reshape(n, out_ml), lengths


@dataclass(frozen=True, eq=False)
class Reverse(Expression):
    """reverse(str): CODEPOINT order reversed (Spark reverse)."""

    child: Expression

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return Reverse(c[0])

    @property
    def dtype(self):
        return self.child.dtype

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        ml = c.data.shape[1]
        cps, nchars = _codepoints(c)
        pos = jnp.arange(cps.shape[1], dtype=jnp.int32)[None, :]
        src = jnp.clip(nchars[:, None] - 1 - pos, 0, cps.shape[1] - 1)
        rev = jnp.where(pos < nchars[:, None],
                        jnp.take_along_axis(cps, src, axis=1), 0)
        data, lengths = _encode_utf8(rev, nchars, ml)
        return _string_column(data, lengths, c.validity, c.dtype.max_len)


@dataclass(frozen=True, eq=False)
class Ascii(Expression):
    """ascii(str): codepoint of the first character; 0 for empty."""

    child: Expression

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return Ascii(c[0])

    @property
    def dtype(self):
        return T.INT32

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        # the first character always starts at byte 0 — decode just its
        # (up to 4) bytes, no full-matrix codepoint pass
        ml = c.data.shape[1]

        def byte_at(k):
            b = c.data[:, k].astype(jnp.int32) if k < ml else \
                jnp.zeros(c.data.shape[0], jnp.int32)
            return jnp.where(k < c.lengths, b, 0)

        b0, b1, b2, b3 = byte_at(0), byte_at(1), byte_at(2), byte_at(3)
        cp = _decode_cp(b0, b1, b2, b3)
        # Spark's Ascii is charAt(0) — the first UTF-16 CODE UNIT, i.e.
        # the high surrogate for supplementary-plane characters
        cp = jnp.where(cp > 0xFFFF,
                       0xD800 + ((cp - 0x10000) >> 10), cp)
        first = jnp.where(c.lengths > 0, cp, 0)
        from .base import numeric_column
        return numeric_column(first.astype(jnp.int32), c.validity, T.INT32)


@dataclass(frozen=True, eq=False)
class Chr(Expression):
    """chr(n): character with codepoint n % 256; negative n -> empty
    (Spark chr semantics; 128-255 encode as two UTF-8 bytes)."""

    child: Expression

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return Chr(c[0])

    @property
    def dtype(self):
        return T.string(2)

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        n = c.data.astype(jnp.int64)
        cp = jnp.where(n < 0, -1, n % 256).astype(jnp.int32)
        counts = jnp.where(cp >= 0, 1, 0).astype(jnp.int32)
        data, lengths = _encode_utf8(
            jnp.maximum(cp, 0)[:, None], counts, 2)
        return _string_column(data, lengths, c.validity, 2)


@dataclass(frozen=True, eq=False)
class OctetLength(Expression):
    """octet_length / bit_length: BYTES, unlike char length."""

    child: Expression
    bits: bool = False

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return OctetLength(c[0], self.bits)

    @property
    def dtype(self):
        return T.INT32

    def eval(self, batch, ctx=EvalContext()):
        from .base import numeric_column
        c = self.child.eval(batch, ctx)
        v = c.lengths.astype(jnp.int32)
        if self.bits:
            v = v * 8
        return numeric_column(v, c.validity, T.INT32)


@dataclass(frozen=True, eq=False)
class Levenshtein(Expression):
    """levenshtein(a, b): edit distance over CODEPOINTS.

    DP rows advance in a fori_loop; the insertion chain inside a row —
    normally a sequential j-scan — vectorizes as a prefix-min of
    (cand[j] - j) (min-plus algebra), so each of the max_len iterations
    is pure elementwise + cummin work."""

    left: Expression
    right: Expression

    @property
    def children(self):
        return (self.left, self.right)

    def with_children(self, c):
        return Levenshtein(c[0], c[1])

    @property
    def dtype(self):
        return T.INT32

    def eval(self, batch, ctx=EvalContext()):
        from .base import numeric_column
        a = self.left.eval(batch, ctx)
        b = self.right.eval(batch, ctx)
        cpa, la = _codepoints(a)
        cpb, lb = _codepoints(b)
        n, mla = cpa.shape
        mlb = cpb.shape[1]
        jpos = jnp.arange(mlb + 1, dtype=jnp.int32)[None, :]
        row0 = jnp.broadcast_to(jpos, (n, mlb + 1)).astype(jnp.int32)
        ans0 = row0     # rows with la == 0

        def body(i, carry):
            row, ans = carry
            ca = cpa[:, i][:, None]
            cost = jnp.where(cpb == ca, 0, 1)
            delete = row[:, 1:] + 1
            sub = row[:, :-1] + cost
            cand = jnp.concatenate(
                [jnp.full((n, 1), i + 1, jnp.int32),
                 jnp.minimum(delete, sub)], axis=1)
            # insertion chain new[j] = min_k<=j cand[k] + (j - k)
            t = cand - jpos
            new_row = jax.lax.cummin(t, axis=1) + jpos
            ans = jnp.where((i + 1 == la)[:, None], new_row, ans)
            return new_row, ans

        _, ans = jax.lax.fori_loop(0, mla, body, (row0, ans0))
        out = jnp.take_along_axis(
            ans, jnp.clip(lb, 0, mlb)[:, None], axis=1)[:, 0]
        return numeric_column(out.astype(jnp.int32),
                              a.validity & b.validity, T.INT32)


_SOUNDEX_CODE = [0] * 128
for _letters, _code in (("BFPV", 1), ("CGJKQSXZ", 2), ("DT", 3), ("L", 4),
                        ("MN", 5), ("R", 6), ("HW", 7)):
    for _ch in _letters:
        _SOUNDEX_CODE[ord(_ch)] = _code


@dataclass(frozen=True, eq=False)
class Soundex(Expression):
    """soundex(str): first letter + 3 digits (Spark's UTF8String.soundex:
    H/W do not separate duplicate codes, vowels do; a non-letter first
    character returns the input unchanged)."""

    child: Expression

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return Soundex(c[0])

    @property
    def dtype(self):
        return T.string(max(self.child.dtype.max_len, 4))

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        n, ml = c.data.shape
        pos = jnp.arange(ml, dtype=jnp.int32)[None, :]
        in_str = pos < c.lengths[:, None]
        up = jnp.where((c.data >= ord("a")) & (c.data <= ord("z")),
                       c.data - 32, c.data).astype(jnp.int32)
        is_letter = (up >= ord("A")) & (up <= ord("Z")) & in_str
        table = jnp.asarray(_SOUNDEX_CODE, jnp.int32)
        codes = jnp.where(is_letter, jnp.take(table, jnp.clip(up, 0, 127)),
                          -1)

        first = up[:, 0]
        first_is_letter = is_letter[:, 0]

        def body(i, carry):
            emitted, last, digits = carry
            code = codes[:, i]
            is_l = is_letter[:, i]
            emit = is_l & (code >= 1) & (code <= 6) & (code != last)
            emit = emit & (emitted < 3) & (i > 0)
            slot = jnp.clip(emitted, 0, 2)
            newd = digits.at[jnp.arange(n), slot].set(
                jnp.where(emit, code, digits[jnp.arange(n), slot]))
            emitted = emitted + emit.astype(jnp.int32)
            # vowels AND non-letters inside the string reset the
            # duplicate tracker (Spark's UTF8String.soundex sets
            # lastCode='0' for every non-letter byte); H/W (7) keep it;
            # consonants set it
            in_row = pos[0, i] < c.lengths
            non_letter = in_row & ~is_l
            last = jnp.where(is_l & (code >= 1) & (code <= 6), code,
                             jnp.where((is_l & (code == 0)) | non_letter,
                                       -1, last))
            return emitted, last, newd

        init_last = jnp.where(first_is_letter,
                              codes[:, 0], jnp.int32(-1))
        emitted, _, digits = jax.lax.fori_loop(
            0, ml, body,
            (jnp.zeros(n, jnp.int32), init_last,
             jnp.zeros((n, 3), jnp.int32)))

        out_ml = self.dtype.max_len
        sx = jnp.zeros((n, out_ml), jnp.uint8)
        sx = sx.at[:, 0].set(first.astype(jnp.uint8))
        for k in range(3):
            sx = sx.at[:, k + 1].set(
                (jnp.where(k < emitted, digits[:, k], 0)
                 + ord("0")).astype(jnp.uint8))
        sx_len = jnp.full(n, 4, jnp.int32)
        # non-letter first char: pass the input through unchanged
        pad = jnp.zeros((n, max(out_ml - ml, 0)), jnp.uint8)
        orig = jnp.concatenate([c.data, pad], axis=1)[:, :out_ml]
        data = jnp.where(first_is_letter[:, None], sx, orig)
        lengths = jnp.where(first_is_letter, sx_len, c.lengths)
        return _string_column(data, lengths, c.validity, out_ml)


@dataclass(frozen=True, eq=False)
class ConcatWs(Expression):
    """concat_ws(sep, s1, s2, ...): skips NULL inputs (unlike concat);
    null only when the separator is null (reference: GpuOverrides
    concat_ws rule). Literal separator."""

    sep: Expression
    exprs: Tuple[Expression, ...]

    @property
    def children(self):
        return (self.sep,) + self.exprs

    def with_children(self, c):
        return ConcatWs(c[0], tuple(c[1:]))

    @property
    def nullable(self):
        return self.sep.nullable

    def device_unsupported_reason(self):
        from .base import Literal
        if not isinstance(self.sep, Literal):
            return "concat_ws separator must be a literal"
        return None

    def _sep(self):
        from .base import Literal
        assert isinstance(self.sep, Literal)
        if self.sep.value is None:
            return None          # null separator -> all-null result
        return str(self.sep.value).encode("utf-8")

    @property
    def dtype(self):
        from .base import Literal
        total = sum(e.dtype.max_len for e in self.exprs)
        if isinstance(self.sep, Literal):
            sep_len = len(self._sep() or b"")
        else:
            sep_len = self.sep.dtype.max_len   # planner still needs a type
        total += sep_len * max(len(self.exprs) - 1, 0)
        return T.string(max(total, 1))

    def eval(self, batch, ctx=EvalContext()):
        sep = self._sep()
        out_ml = self.dtype.max_len
        if sep is None:
            n = batch.capacity
            return _string_column(jnp.zeros((n, out_ml), jnp.uint8),
                                  jnp.zeros(n, jnp.int32),
                                  jnp.zeros(n, bool), out_ml)
        cols = [e.eval(batch, ctx) for e in self.exprs]
        n = batch.capacity
        flat = jnp.zeros(n * out_ml + 1, jnp.uint8)
        offset = jnp.zeros(n, jnp.int32)
        rows = jnp.arange(n)[:, None]
        sep_a = jnp.asarray(bytearray(sep), jnp.uint8) if sep else None
        seen = jnp.zeros(n, bool)    # a non-null value already emitted
        for c in cols:
            ml = c.data.shape[1]
            lengths = jnp.where(c.validity, c.lengths, 0)
            # separator before this value when something precedes it
            if sep_a is not None and len(sep) > 0:
                put_sep = seen & c.validity
                tgt = jnp.where(put_sep[:, None],
                                rows * out_ml + offset[:, None]
                                + jnp.arange(len(sep))[None, :],
                                n * out_ml)
                flat = flat.at[tgt.reshape(-1)].set(
                    jnp.broadcast_to(sep_a, (n, len(sep))).reshape(-1),
                    mode="drop")
                offset = offset + jnp.where(put_sep, len(sep), 0)
            in_str = (jnp.arange(ml)[None, :] < lengths[:, None]) \
                & c.validity[:, None]
            target = jnp.where(in_str,
                               rows * out_ml + offset[:, None]
                               + jnp.arange(ml)[None, :],
                               n * out_ml)
            flat = flat.at[target.reshape(-1)].set(c.data.reshape(-1),
                                                   mode="drop")
            offset = offset + lengths
            seen = seen | c.validity
        out = flat[: n * out_ml].reshape(n, out_ml)
        validity = batch.row_mask()
        return _string_column(out, jnp.minimum(offset, out_ml), validity,
                              out_ml)


@dataclass(frozen=True, eq=False)
class SubstringIndex(Expression):
    """substring_index(str, delim, count): prefix before the count-th
    delimiter (count<0: suffix after the |count|-th from the right).
    Literal delimiter (reference: GpuSubstringIndex — same restriction)."""

    child: Expression
    delim: Expression
    count: Expression

    @property
    def children(self):
        return (self.child, self.delim, self.count)

    def with_children(self, c):
        return SubstringIndex(c[0], c[1], c[2])

    @property
    def dtype(self):
        return self.child.dtype

    def device_unsupported_reason(self):
        from .base import Literal
        if not (isinstance(self.delim, Literal)
                and isinstance(self.count, Literal)):
            return "substring_index delimiter/count must be literals"
        return None

    def _parts(self):
        from .base import Literal
        assert isinstance(self.delim, Literal) and \
            isinstance(self.count, Literal)
        return str(self.delim.value).encode("utf-8"), int(self.count.value)

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        delim, cnt = self._parts()
        ml = c.data.shape[1]
        if cnt == 0 or not delim:
            return _string_column(jnp.zeros_like(c.data),
                                  jnp.zeros_like(c.lengths), c.validity, ml)
        m = _window_match(c.data, c.lengths, delim)
        occ = jnp.cumsum(m.astype(jnp.int32), axis=1)   # occurrences so far
        total = occ[:, -1]
        k = len(delim)
        idx = jnp.arange(ml)[None, :]
        if cnt > 0:
            # end = start of the cnt-th occurrence (whole string if fewer)
            hit = m & (occ == cnt)
            pos = jnp.where(jnp.any(hit, axis=1),
                            jnp.argmax(hit, axis=1).astype(jnp.int32),
                            c.lengths)
            data = jnp.where(idx < pos[:, None], c.data, 0)
            return _string_column(data, pos, c.validity, ml)
        # negative: start after the (total+cnt)-th occurrence's end
        want = total + cnt   # index of the occurrence BEFORE the suffix
        hit = m & (occ == jnp.maximum(want, 0)[:, None] + 1)
        has = (want >= 0) & jnp.any(hit, axis=1)
        start = jnp.where(has,
                          jnp.argmax(hit, axis=1).astype(jnp.int32) + k,
                          0)
        new_len = jnp.maximum(c.lengths - start, 0)
        # shift left by start (per-row roll via gather)
        gather_idx = jnp.clip(idx + start[:, None], 0, ml - 1)
        data = jnp.take_along_axis(c.data, gather_idx, axis=1)
        data = jnp.where(idx < new_len[:, None], data, 0)
        return _string_column(data, new_len, c.validity, ml)


_HEX_DIGITS = np.frombuffer(b"0123456789ABCDEF", np.uint8)


@dataclass(frozen=True, eq=False)
class Hex(Expression):
    """hex(bigint) / hex(string): uppercase hex, no leading zeros for
    numbers (two's complement for negatives), per-byte for strings."""

    child: Expression

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return Hex(c[0])

    @property
    def dtype(self):
        from ..types import TypeKind as K
        if self.child.dtype.kind is K.STRING:
            return T.string(max(self.child.dtype.max_len * 2, 1))
        return T.string(16)

    def eval(self, batch, ctx=EvalContext()):
        from ..types import TypeKind as K
        c = self.child.eval(batch, ctx)
        if self.child.dtype.kind is K.STRING:
            ml = c.data.shape[1]
            hi = jnp.take(_HEX_DIGITS, (c.data >> 4).astype(jnp.int32))
            lo = jnp.take(_HEX_DIGITS, (c.data & 15).astype(jnp.int32))
            out = jnp.stack([hi, lo], axis=2).reshape(c.data.shape[0],
                                                      2 * ml)
            return _string_column(out, c.lengths * 2, c.validity, 2 * ml)
        v = c.data.astype(jnp.int64).astype(jnp.uint64)
        n = batch.capacity
        digs = []
        for d in range(16):
            nib = ((v >> jnp.uint64(4 * (15 - d))) & jnp.uint64(15)) \
                .astype(jnp.int32)
            digs.append(jnp.take(_HEX_DIGITS, nib))
        mat = jnp.stack(digs, axis=1)                       # [n, 16]
        nz = mat != ord("0")
        first = jnp.where(jnp.any(nz, axis=1),
                          jnp.argmax(nz, axis=1).astype(jnp.int32), 15)
        length = 16 - first
        idx = jnp.arange(16)[None, :]
        shifted = jnp.take_along_axis(
            mat, jnp.clip(idx + first[:, None], 0, 15), axis=1)
        data = jnp.where(idx < length[:, None], shifted, 0)
        return _string_column(data, length, c.validity, 16)


@dataclass(frozen=True, eq=False)
class Bin(Expression):
    """bin(bigint): binary string, no leading zeros (two's complement)."""

    child: Expression

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return Bin(c[0])

    @property
    def dtype(self):
        return T.string(64)

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        v = c.data.astype(jnp.int64).astype(jnp.uint64)
        bits = []
        for d in range(64):
            b = ((v >> jnp.uint64(63 - d)) & jnp.uint64(1)).astype(jnp.uint8)
            bits.append(b + ord("0"))
        mat = jnp.stack(bits, axis=1)
        nz = mat != ord("0")
        first = jnp.where(jnp.any(nz, axis=1),
                          jnp.argmax(nz, axis=1).astype(jnp.int32), 63)
        length = 64 - first
        idx = jnp.arange(64)[None, :]
        shifted = jnp.take_along_axis(
            mat, jnp.clip(idx + first[:, None], 0, 63), axis=1)
        data = jnp.where(idx < length[:, None], shifted, 0)
        return _string_column(data, length, c.validity, 64)


@dataclass(frozen=True, eq=False)
class Conv(Expression):
    """conv(numstr, from_base, to_base): base conversion with LITERAL
    bases 2..36 (reference: GpuConv — same literal restriction). Follows
    Spark: parses the longest valid prefix, empty/invalid -> "0"; negative
    inputs are interpreted via unsigned 64-bit wraparound when to_base>0."""

    child: Expression
    from_base: Expression
    to_base: Expression

    @property
    def children(self):
        return (self.child, self.from_base, self.to_base)

    def with_children(self, c):
        return Conv(c[0], c[1], c[2])

    @property
    def dtype(self):
        return T.string(65)

    def device_unsupported_reason(self):
        from .base import Literal
        if not (isinstance(self.from_base, Literal)
                and isinstance(self.to_base, Literal)):
            return "conv bases must be literals"
        return None

    def _bases(self):
        from .base import Literal
        assert isinstance(self.from_base, Literal) and \
            isinstance(self.to_base, Literal)
        return int(self.from_base.value), int(self.to_base.value)

    def eval(self, batch, ctx=EvalContext()):
        fb, tb = self._bases()
        c = self.child.eval(batch, ctx)
        validity = c.validity
        if not (2 <= fb <= 36 and 2 <= abs(tb) <= 36):
            return _string_column(
                jnp.zeros((batch.capacity, 65), jnp.uint8),
                jnp.zeros(batch.capacity, jnp.int32),
                jnp.zeros(batch.capacity, bool), 65)
        data, lengths = c.data, c.lengths
        n, ml = data.shape
        # parse: optional '-', then digits of from_base (longest prefix)
        neg = (lengths > 0) & (data[:, 0] == ord("-"))
        start = neg.astype(jnp.int32)
        up = jnp.where((data >= ord("a")) & (data <= ord("z")),
                       data - 32, data)
        digit = jnp.where((up >= ord("0")) & (up <= ord("9")),
                          up - ord("0"),
                          jnp.where((up >= ord("A")) & (up <= ord("Z")),
                                    up - ord("A") + 10, 99)).astype(jnp.int32)
        idx = jnp.arange(ml)[None, :]
        in_range = (idx >= start[:, None]) & (idx < lengths[:, None])
        ok = in_range & (digit < fb)
        # longest valid prefix: stop at first non-digit
        bad_before = jnp.cumsum((in_range & ~(digit < fb)).astype(jnp.int32),
                                axis=1)
        use = ok & (bad_before == 0)
        v = jnp.zeros(n, jnp.uint64)
        for j in range(ml):
            d = digit[:, j].astype(jnp.uint64)
            v = jnp.where(use[:, j], v * jnp.uint64(fb) + d, v)
        any_digit = jnp.any(use, axis=1)
        # Spark: negative input with to_base>0 wraps as unsigned 64-bit
        v = jnp.where(neg & any_digit, (~v) + jnp.uint64(1), v)
        signed_out = tb < 0
        ab = abs(tb)
        if signed_out:
            sv = v.astype(jnp.int64)
            out_neg = sv < 0
            mag = jnp.where(out_neg, (-sv), sv).astype(jnp.uint64)
        else:
            out_neg = jnp.zeros(n, bool)
            mag = v
        # emit digits most-significant first into 64 slots
        digs = []
        cur = mag
        for _ in range(64):
            digs.append((cur % jnp.uint64(ab)).astype(jnp.int32))
            cur = cur // jnp.uint64(ab)
        mat = jnp.stack(digs[::-1], axis=1)                  # [n, 64]
        ch = jnp.take(_HEX_DIGITS, jnp.clip(mat, 0, 15))
        # digits >= 16 need letters beyond F
        ch = jnp.where(mat >= 16, (mat - 10 + ord("A")).astype(jnp.uint8),
                       ch)
        nz = mat != 0
        first = jnp.where(jnp.any(nz, axis=1),
                          jnp.argmax(nz, axis=1).astype(jnp.int32), 63)
        length = 64 - first
        pos = jnp.arange(65)[None, :]
        shifted = jnp.take_along_axis(
            jnp.pad(ch, ((0, 0), (0, 1))),
            jnp.clip(pos + first[:, None], 0, 64), axis=1)
        body = jnp.where(pos < length[:, None], shifted, 0)
        # prepend '-' for signed negative output
        out = jnp.where(out_neg[:, None],
                        jnp.concatenate([jnp.full((n, 1), ord("-"),
                                                  jnp.uint8),
                                         body[:, :-1]], axis=1),
                        body)
        out_len = length + out_neg.astype(jnp.int32)
        out_len = jnp.where(any_digit, out_len, 1)
        out = jnp.where(any_digit[:, None], out,
                        jnp.pad(jnp.full((n, 1), ord("0"), jnp.uint8),
                                ((0, 0), (0, 64))))
        return _string_column(out, out_len, validity, 65)


@dataclass(frozen=True, eq=False)
class FindInSet(Expression):
    """find_in_set(str, set): 1-based index of ``str`` within the
    comma-separated ``set``, 0 when absent or when ``str`` contains a
    comma (reference: GpuStringFindInSet / stringFunctions.scala)."""

    child: Expression = None
    set: Expression = None

    @property
    def children(self):
        return (self.child, self.set)

    def with_children(self, c):
        return FindInSet(c[0], c[1])

    @property
    def dtype(self):
        return T.INT32

    def eval(self, batch, ctx=EvalContext()):
        from .base import numeric_column
        q = self.child.eval(batch, ctx)
        s = self.set.eval(batch, ctx)
        comma = jnp.uint8(ord(","))
        n, mls = s.data.shape
        mlq = q.data.shape[1]
        pos = jnp.arange(mls)[None, :]
        in_set = pos < s.lengths[:, None]
        is_comma = (s.data == comma) & in_set
        # dynamic-needle window equality: m[row, p] = set[p:p+qlen] == str
        m = jnp.ones((n, mls), bool)
        for j in range(mlq):
            shifted = jnp.roll(s.data, -j, axis=1)
            m = m & ((jnp.asarray(j) >= q.lengths[:, None])
                     | (shifted == q.data[:, j:j + 1]))
        # entry starts: position 0 or right after a comma
        start = jnp.concatenate(
            [jnp.ones((n, 1), bool), is_comma[:, :-1]], axis=1) & in_set
        # entry must END exactly at p+qlen (comma or end of set)
        endp = pos + q.lengths[:, None]
        at_end = endp == s.lengths[:, None]
        ml_idx = jnp.clip(endp, 0, mls - 1)
        comma_at_end = jnp.take_along_axis(is_comma, ml_idx, axis=1) & \
            (endp < mls)
        hit = start & m & (at_end | comma_at_end) & \
            (endp <= s.lengths[:, None])
        entry_id = jnp.cumsum(is_comma.astype(jnp.int32), axis=1) - \
            is_comma.astype(jnp.int32)
        found = jnp.any(hit, axis=1)
        first = jnp.argmax(hit, axis=1)
        idx = jnp.take_along_axis(entry_id, first[:, None], axis=1)[:, 0] + 1
        # the empty entry STARTING at position len(set) (empty set, or a
        # trailing comma) lies outside the position grid: handle the
        # virtual end slot for empty needles explicitly
        n_entries = jnp.sum(is_comma.astype(jnp.int32), axis=1) + 1
        last_ix = jnp.clip(s.lengths - 1, 0, mls - 1)
        end_empty = (s.lengths == 0) | jnp.take_along_axis(
            is_comma, last_ix[:, None], axis=1)[:, 0]
        end_hit = (q.lengths == 0) & end_empty
        idx = jnp.where(found, idx, jnp.where(end_hit, n_entries, 0))
        found = found | end_hit
        has_comma = jnp.any((q.data == comma) &
                            (jnp.arange(mlq)[None, :] < q.lengths[:, None]),
                            axis=1)
        r = jnp.where(found & ~has_comma, idx, 0)
        return numeric_column(r.astype(jnp.int32),
                              q.validity & s.validity, T.INT32)


@dataclass(frozen=True, eq=False)
class Empty2Null(Expression):
    """'' -> NULL (Spark inserts this around Hive text writes; reference:
    GpuEmpty2Null)."""

    child: Expression = None

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return Empty2Null(c[0])

    @property
    def dtype(self):
        return self.child.dtype

    @property
    def nullable(self):
        return True

    def eval(self, batch, ctx=EvalContext()):
        c = self.child.eval(batch, ctx)
        return c.replace(validity=c.validity & (c.lengths > 0))


@dataclass(frozen=True, eq=False)
class StringToMap(Expression):
    """str_to_map(str, pair_delim, kv_delim) with LITERAL single-byte
    delimiters -> map<string,string> (reference: GpuStringToMap,
    GpuOverrides.scala:2507; same literal-delimiter restriction).

    Device map layout for string elements: keys ride ``data`` and values
    ``data2`` as [cap, max_entries, max_len] byte tensors, zero-padded so
    element lengths are derivable from trailing zeros (the canonical
    string padding _string_column already guarantees). Entries without a
    kv delimiter get the whole entry as key and a NULL value, like Spark.
    Value NULL-ness is encoded as an all-0xFF sentinel length marker in
    the first byte... no: a value is NULL iff the entry had no kv_delim,
    recorded by a 0xFF pad in data2's first byte being impossible — so
    instead the kernel stores value length+1 in a trailing lane; see
    ``MapStringOps`` consumers."""

    child: Expression = None
    pair_delim: str = ","
    kv_delim: str = ":"
    max_entries: int = 16

    @property
    def children(self):
        return (self.child,)

    def with_children(self, c):
        return StringToMap(c[0], self.pair_delim, self.kv_delim,
                           self.max_entries)

    def device_unsupported_reason(self):
        if len(self.pair_delim.encode()) != 1 or \
                len(self.kv_delim.encode()) != 1:
            return "str_to_map: delimiters must be single-byte literals"
        return None

    @property
    def dtype(self):
        ml = self.child.dtype.max_len or 64
        return T.map_(T.string(ml), T.string(ml), self.max_entries)

    def eval(self, batch, ctx=EvalContext()):
        import jax
        c = self.child.eval(batch, ctx)
        pd = jnp.uint8(self.pair_delim.encode()[0])
        kd = jnp.uint8(self.kv_delim.encode()[0])
        n, ml = c.data.shape
        E = self.max_entries
        pos = jnp.arange(ml, dtype=jnp.int32)[None, :]
        in_str = pos < c.lengths[:, None]
        is_pd = (c.data == pd) & in_str
        # entry index of each byte (delimiters belong to the PREVIOUS
        # entry's boundary, not to either entry body)
        entry_id = jnp.cumsum(is_pd.astype(jnp.int32), axis=1) - \
            is_pd.astype(jnp.int32)
        n_entries = jnp.where(
            c.lengths > 0, entry_id[:, -1] + 1,
            jnp.where(c.validity, 1, 0))
        ctx.report((n_entries > E) & c.validity,
                   "CAPACITY_str_to_map_entries", always=True)
        # offset of each byte within its entry: pos - entry start
        starts = jnp.where(is_pd, pos + 1, 0)
        run_start = jax.lax.cummax(starts, axis=1)
        off = pos - run_start
        eid_c = jnp.clip(entry_id, 0, E - 1)
        rows = jnp.repeat(jnp.arange(n, dtype=jnp.int32)[:, None], ml, 1)
        # first kv-delimiter offset per entry (ml+1 = none -> NULL value)
        is_kd = (c.data == kd) & in_str & ~is_pd
        kv_flat = jnp.full(n * E, ml + 1, jnp.int32).at[
            jnp.where(is_kd, rows * E + eid_c, n * E).reshape(-1)
        ].min(off.reshape(-1), mode="drop")
        kv_off = kv_flat.reshape(n, E)
        kv_here = jnp.take_along_axis(kv_off, eid_c, axis=1)
        body = in_str & ~is_pd
        is_key = body & (off < kv_here)
        is_val = body & (off > kv_here)
        voff = off - kv_here - 1
        # dropped-target scatters: non-member bytes aim out of bounds
        keys = jnp.zeros((n, E, ml), jnp.uint8).at[
            rows, eid_c, jnp.where(is_key, off, ml)].set(
            c.data, mode="drop")
        vals = jnp.zeros((n, E, ml), jnp.uint8).at[
            rows, eid_c, jnp.where(is_val, voff, ml)].set(
            c.data, mode="drop")
        # NULL value (entry without kv delimiter): 0xFF first-byte marker
        # (0xFF never occurs in valid UTF-8, making the sentinel exact)
        slot = jnp.arange(E, dtype=jnp.int32)[None, :]
        no_kv = (kv_off > ml) & (slot < jnp.minimum(n_entries, E)[:, None])
        vals = vals.at[:, :, 0].set(
            jnp.where(no_kv, jnp.uint8(0xFF), vals[:, :, 0]))
        lengths = jnp.where(c.validity, jnp.minimum(n_entries, E), 0)
        return DeviceColumn(keys, c.validity, lengths, self.dtype, vals)


def string_elem_lengths(b3):
    """Derive per-element byte lengths of a [n, E, ml] zero-padded string
    tensor (canonical padding; valid UTF-8 holds no NUL): length = 1 +
    index of last nonzero byte."""
    ml = b3.shape[-1]
    nz = b3 != 0
    last = ml - 1 - jnp.argmax(nz[..., ::-1].astype(jnp.int32), axis=-1)
    return jnp.where(jnp.any(nz, axis=-1), last + 1, 0).astype(jnp.int32)
