"""A lexicographic sort built from single-key 32-bit sorts.

XLA:TPU compiles `lax.sort` slower with every key operand and far
slower with 64-bit ones: at 32,768 rows one uint32 key and a
permutation take 19 s, three 32-bit keys 74 s, one float64 key 149 s at
131,072, and eleven 32-bit keys did not finish in half an hour (AOT for
a v5e in the sandbox, PERF.md section 6, PR 32).  A scan over the keys'
32-bit words, least significant first, each step one stable single-key
sort of (word, permutation), compiles once whatever the number of words
(32 s for ten words at 131,072 rows) and gives the same stable
permutation.  Two users: the TopK merge of several or of 64-bit keys
(`exec/sort.py`) and the keyed aggregate's reduce over key tuples
(`exec/aggregate.py`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_SIGN = 0x80000000


def _canonical(x):
    """`x` with one zero and one NaN, as `lax.sort` orders floats: the
    two zeros tie and every NaN sorts above +inf."""
    x = jnp.where(x == 0, jnp.zeros((), x.dtype), x)
    return jnp.where(jnp.isnan(x), jnp.full((), jnp.nan, x.dtype), x)


def _f32_word(x):
    """uint32 image of a float32 (`_canonical`), ascending."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >= _SIGN, ~bits, bits | jnp.uint32(_SIGN))


def _f64_words(x):
    """Words of a float64, most significant first.  Where the backend
    has native 64-bit types (the CPU) they are the halves of the value's
    bit image; XLA:TPU keeps a float64 as an (f32 hi, f32 lo) pair and
    lowers no 64-bit bitcast (`batch._f64_split`: the pair IS the
    device's representation), so there they are the images of the two
    halves, which order as their sum does."""
    x = _canonical(x)
    if jax.default_backend() == "cpu":
        bits = lax.bitcast_convert_type(x, jnp.uint64)
        sign = jnp.uint64(1 << 63)
        image = jnp.where(bits >= sign, ~bits, bits | sign)
        return [(image >> jnp.uint64(32)).astype(jnp.uint32),
                image.astype(jnp.uint32)]
    hi = x.astype(jnp.float32)
    lo = (x - hi.astype(jnp.float64)).astype(jnp.float32)
    # inf - inf: the high half alone is the value
    lo = jnp.where(jnp.isfinite(hi), lo, jnp.float32(0))
    return [_f32_word(hi), _f32_word(lo)]


def key_words(op) -> list:
    """One sort operand as uint32 words, most significant first, whose
    lexicographic order is the operand's ascending order."""
    dt = op.dtype
    if dt == jnp.bool_:
        return [op.astype(jnp.uint32)]
    if dt == jnp.float64:
        return _f64_words(op)
    if jnp.issubdtype(dt, jnp.floating):
        return [_f32_word(_canonical(op.astype(jnp.float32)))]
    if dt.itemsize <= 4:
        if jnp.issubdtype(dt, jnp.signedinteger):
            return [op.astype(jnp.int32).astype(jnp.uint32)
                    ^ jnp.uint32(_SIGN)]
        return [op.astype(jnp.uint32)]
    high = (op >> 32).astype(jnp.uint32)
    if jnp.issubdtype(dt, jnp.signedinteger):
        high = high ^ jnp.uint32(_SIGN)
    return [high, op.astype(jnp.uint32)]


def lex_perm(words: list):
    """The stable permutation (int32) that puts rows in ascending
    lexicographic order of `words` (uint32 arrays of one length, most
    significant first)."""
    n = words[0].shape[0]

    def step(perm, word):
        _, perm = lax.sort((word[perm], perm), num_keys=1, is_stable=True)
        return perm, None

    perm, _ = lax.scan(step, jnp.arange(n, dtype=jnp.int32),
                       jnp.stack(words[::-1]))
    return perm
