"""Hash tokenizer stub: surviving log rows → LM token streams.

A real deployment would tokenize the log's text payload; the assigned-arch
contract allows modality frontends to be stubs. This one is deterministic
and cheap: each surviving row is mixed into ``tokens_per_row`` int tokens via
a splitmix-style integer hash of its column values, so the LM examples are
(a) a pure function of the filtered stream and (b) reproducible across
restarts — which the fault-tolerance tests rely on.

Two implementations of the SAME hash:

  ``rows_to_tokens``        — numpy, host path (dense survivor rows in).
  ``tokens_from_padded``    — jitted jax path over the padded ``[S, C, cap]``
                              survivor buffers + counts that device-side
                              compaction emits, so the tokenize/pack stage
                              runs on the mesh and the batch columns never
                              round-trip through a host boolean index (the
                              "compaction-aware downstream stage" of the
                              single-pass ingestion path). Valid rows are
                              selected by count masking and the tokens are
                              packed shard-major with the same O(N) cumsum
                              scatter the compactor uses — bit-identical to
                              the host stream (pinned by tests).

The hash is defined on the u64 bit pattern of the f64-widened column values
(the numpy path's ``astype(float64).view(uint64)``). The jax path computes
the SAME u64 arithmetic in **u32 limb pairs** — widening f32 bit patterns
to f64 bit patterns by integer exponent/mantissa surgery, 64-bit
add/xor/shift/multiply via (hi, lo) u32 carries, and the final
``% vocab_size`` as a base-256 byte fold (exact for vocab < 2**24) — so it
traces WITHOUT ``jax.experimental.enable_x64`` and lowers on TPU, where
u64 is unsupported. Bit-exactness against the u64 host path is pinned by
``tests/test_tokenizer_u32.py``.
"""

from __future__ import annotations

import functools

import numpy as np

_GAMMA = np.uint64(0x9E3779B97F4A7C15)
_MIX1 = np.uint64(0xBF58476D1CE4E5B9)
_MIX2 = np.uint64(0x94D049BB133111EB)


def _max_device_vocab() -> int:
    """Byte-fold modulo ceiling — single-sourced in ``core.plan``
    (imported lazily: this module stays a numpy-only leaf)."""
    from repro.core.plan import MAX_DEVICE_VOCAB
    return MAX_DEVICE_VOCAB


def _splitmix(x: np.ndarray) -> np.ndarray:
    x = (x + _GAMMA).astype(np.uint64)
    x ^= x >> np.uint64(30)
    x = (x * _MIX1) & np.uint64(0xFFFFFFFFFFFFFFFF)
    x ^= x >> np.uint64(27)
    x = (x * _MIX2) & np.uint64(0xFFFFFFFFFFFFFFFF)
    x ^= x >> np.uint64(31)
    return x


def rows_to_tokens(columns: np.ndarray, vocab_size: int,
                   tokens_per_row: int = 8) -> np.ndarray:
    """f32[C, R] → i32[R * tokens_per_row] token ids in [0, vocab_size)."""
    if columns.shape[1] == 0:
        return np.zeros((0,), np.int32)
    base = np.zeros(columns.shape[1], np.uint64)
    for c in range(columns.shape[0]):
        base = _splitmix(base ^ columns[c].astype(np.float64).view(np.uint64))
    toks = []
    h = base
    for _ in range(tokens_per_row):
        h = _splitmix(h)
        toks.append((h % np.uint64(vocab_size)).astype(np.int32))
    return np.stack(toks, axis=1).reshape(-1)


# ====================================================== u32-limb device path
@functools.cache
def _limb_ops():
    """u64 arithmetic as (hi, lo) u32 limb pairs — TPU-lowerable primitives.

    Everything here is exact mod-2^64 integer math: the splitmix constants
    are split into static u32 halves, 64-bit multiply goes through the
    classic 16-bit-limb mulhi decomposition (all intermediates < 2^32), and
    f32→f64 widening is IEEE bit surgery (sign/exponent/mantissa re-bias,
    including subnormal renormalization via count-leading-zeros) so no f64
    value ever exists in the traced program.
    """
    import jax.numpy as jnp
    from jax import lax

    # numpy scalars, NOT jnp: the closure is functools.cache'd, and a
    # jnp constant materialized while some outer trace is live would be a
    # tracer baked into the cache — poisoning every later call
    # (UnexpectedTracerError). numpy scalars are concrete in every
    # context and inline into traces as literals.
    u32 = np.uint32
    M16 = u32(0xFFFF)

    def mul32_wide(a, b):
        """u32 × u32 → (hi, lo) full 64-bit product, via 16-bit limbs."""
        a0, a1 = a & M16, a >> u32(16)
        b0, b1 = b & M16, b >> u32(16)
        t = a0 * b0
        w0 = t & M16
        t = a1 * b0 + (t >> u32(16))
        w2 = t >> u32(16)
        t = a0 * b1 + (t & M16)
        hi = a1 * b1 + w2 + (t >> u32(16))
        lo = (t << u32(16)) | w0
        return hi, lo

    def add64(h, l, ch: int, cl: int):
        """(h,l) + static u64 constant (given as two python ints)."""
        lo = l + u32(cl)
        carry = (lo < l).astype(jnp.uint32)
        return h + u32(ch) + carry, lo

    def shr64(h, l, k: int):
        """logical right shift by static 0 < k < 32."""
        return h >> u32(k), (l >> u32(k)) | (h << u32(32 - k))

    def mul64(h, l, ch: int, cl: int):
        """(h,l) · static u64 constant, low 64 bits."""
        ph, pl = mul32_wide(l, u32(cl))
        ph = ph + l * u32(ch) + h * u32(cl)
        return ph, pl

    def splitmix64(h, l):
        h, l = add64(h, l, 0x9E3779B9, 0x7F4A7C15)
        sh, sl = shr64(h, l, 30)
        h, l = h ^ sh, l ^ sl
        h, l = mul64(h, l, 0xBF58476D, 0x1CE4E5B9)
        sh, sl = shr64(h, l, 27)
        h, l = h ^ sh, l ^ sl
        h, l = mul64(h, l, 0x94D049BB, 0x133111EB)
        sh, sl = shr64(h, l, 31)
        return h ^ sh, l ^ sl

    def f64_bits_of_f32(x):
        """f32[...] → (hi, lo) u32 IEEE-754 bit pattern of float64(x).

        f32→f64 widening is exact, so the f64 bits are a pure function of
        the f32 bits: re-bias the exponent (+896), shift the mantissa up 29
        bits, and renormalize subnormals (value m·2^-149 becomes a normal
        f64 with exponent p+874 where p = floor(log2 m)). Zeros keep their
        sign; inf/NaN map to exponent 2047 with the payload widened the
        same way (preserving the quiet bit).
        """
        bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
        s_hi = (bits >> u32(31)) << u32(31)
        e = (bits >> u32(23)) & u32(0xFF)
        m = bits & u32(0x7FFFFF)
        hi_wide = s_hi | (m >> u32(3))           # widened mantissa, hi part
        lo_wide = (m & u32(0x7)) << u32(29)      # widened mantissa, lo part
        hi_norm = hi_wide | ((e + u32(896)) << u32(20))
        # NaNs are QUIETED like hardware cvtss2sd does (f64 quiet bit =
        # mantissa bit 51 = hi bit 19); inf (m == 0) is left alone
        quiet = jnp.where((e == u32(255)) & (m != u32(0)),
                          u32(1) << u32(19), u32(0))
        hi_inf = hi_wide | (u32(0x7FF) << u32(20)) | quiet
        # subnormal: renormalize. p = floor(log2 m) in [0, 22]; the f64
        # mantissa is (m - 2^p) << (52 - p), split across the limbs.
        m_safe = jnp.maximum(m, u32(1))          # keep the dead lane defined
        p = u32(31) - lax.clz(m_safe)
        frac = m_safe ^ (u32(1) << p)
        hi_mant = jnp.where(p <= u32(20),
                            frac << jnp.where(p <= u32(20), u32(20) - p,
                                              u32(0)),
                            frac >> jnp.where(p > u32(20), p - u32(20),
                                              u32(0)))
        lo_sub = jnp.where(p >= u32(21),
                           frac << jnp.where(p >= u32(21), u32(52) - p,
                                             u32(0)),
                           u32(0))
        hi_sub = s_hi | ((p + u32(874)) << u32(20)) | (hi_mant & u32(0xFFFFF))
        is_zero = (e == u32(0)) & (m == u32(0))
        is_sub = (e == u32(0)) & (m != u32(0))
        is_inf = e == u32(255)
        hi = jnp.where(is_zero, s_hi,
                       jnp.where(is_sub, hi_sub,
                                 jnp.where(is_inf, hi_inf, hi_norm)))
        lo = jnp.where(is_zero, u32(0), jnp.where(is_sub, lo_sub, lo_wide))
        return hi, lo

    def mod_u64(h, l, v: int):
        """(h·2^32 + l) % v for static 1 <= v < 2^24, by base-256 byte fold
        (r stays < v, so r·256 + byte < 2^32 — never overflows a limb)."""
        assert 1 <= v < _max_device_vocab()
        r = jnp.zeros_like(h)
        for word in (h, l):
            for shift in (24, 16, 8, 0):
                r = (r * u32(256) + ((word >> u32(shift)) & u32(0xFF))) \
                    % u32(v)
        return r

    return splitmix64, f64_bits_of_f32, mod_u64


@functools.cache
def _jit_tokens_from_padded():
    """Build (lazily, once) the jitted u32-limb device tokenizer."""
    import jax
    import jax.numpy as jnp

    splitmix64, f64_bits_of_f32, mod_u64 = _limb_ops()

    @functools.partial(jax.jit,
                       static_argnames=("vocab_size", "tokens_per_row"))
    def tok(packed, counts, *, vocab_size: int, tokens_per_row: int):
        s, c, cap = packed.shape
        # hash every slot (padding rows hash to garbage and are masked out —
        # branch-free, the device way)
        bh = jnp.zeros((s, cap), jnp.uint32)
        bl = jnp.zeros((s, cap), jnp.uint32)
        for ci in range(c):
            xh, xl = f64_bits_of_f32(packed[:, ci, :])
            bh, bl = splitmix64(bh ^ xh, bl ^ xl)
        # a scan, not an unrolled loop: the unrolled 8-token limb program
        # runs ~10^4x slower on the XLA CPU backend than this one
        def next_token(carry, _):
            h, l = splitmix64(*carry)
            return (h, l), mod_u64(h, l, vocab_size).astype(jnp.int32)

        _, toks = jax.lax.scan(next_token, (bh, bl), None,
                               length=tokens_per_row)
        tokens = jnp.moveaxis(toks, 0, -1)           # i32[S, cap, T]
        # valid-count masking + shard-major O(N) pack (same cumsum scatter
        # as the survivor compactor — no sort anywhere in the pipeline)
        valid = (jnp.arange(cap)[None, :] < counts[:, None])   # bool[S, cap]
        flat_valid = jnp.repeat(valid.reshape(-1), tokens_per_row)
        flat = tokens.reshape(-1)
        n = flat.shape[0]
        pos = jnp.cumsum(flat_valid.astype(jnp.int32)) - 1
        dest = jnp.where(flat_valid, pos, n)
        out = jnp.zeros((n + 1,), jnp.int32).at[dest].set(flat, mode="drop")
        total = jnp.sum(counts).astype(jnp.int32) * tokens_per_row
        return out[:n], total

    return tok


def tokens_from_padded(packed, counts, vocab_size: int,
                       tokens_per_row: int = 8):
    """Device tokenize+pack over padded survivor buffers (u32-limb path).

    ``packed``: f32[S, C, cap] (or [C, cap] for a single pipeline — auto-
    promoted), ``counts``: i32[S] valid widths. Returns (tokens i32[S·cap·T]
    with the first ``n_valid`` entries live, n_valid i32[]) — the first
    ``n_valid`` tokens are bit-identical to ``rows_to_tokens`` applied to
    the shard-major concatenation of the valid survivor slices. Traces
    without ``enable_x64`` (u32 limb arithmetic throughout — TPU-lowerable);
    requires ``vocab_size < 2**24``.
    """
    import jax.numpy as jnp

    if not 1 <= vocab_size < _max_device_vocab():
        raise ValueError(
            f"device tokenize needs 1 <= vocab_size < {_max_device_vocab()} "
            f"(u32-limb byte-fold modulo), got {vocab_size}")
    if packed.ndim == 2:
        packed = packed[None]
        counts = jnp.asarray(counts, jnp.int32).reshape((1,))
    return _jit_tokens_from_padded()(
        packed, counts, vocab_size=vocab_size, tokens_per_row=tokens_per_row)
