"""The decode kernel of `kernels/flash_attention.py` (interpret mode on the
CPU) against the masked reference row `_decode_reference`: dtypes, heads x
head_dim, a capacity of one and of several key blocks, and the lengths a
decode batch meets — a slot at its first token, a full slot, a ragged batch,
and an empty slot (no valid key: the uniform average over the cache, which
callers never read). A key block wholly past a slot's length is stepped
over — not copied from HBM, not computed on — and the result is, bit for bit,
that of the kernel with every block live. The compiled kernel is in
tests/test_tpu_compile.py."""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")

SLOTS, BLOCK = 4, 64
LENGTHS = {
    "one": lambda C: [1, 1, 1, 1],
    "full": lambda C: [C, C, C, C],
    "ragged": lambda C: [C // 2 + 3, 1, C, BLOCK],
    "empty": lambda C: [0, C - 1, 0, 2],
}


@pytest.mark.parametrize("lengths", LENGTHS)
@pytest.mark.parametrize("blocks", [1, 3], ids=lambda n: f"{n}blk")
@pytest.mark.parametrize("heads,dim", [(4, 64), (16, 64), (8, 128)],
                         ids=lambda x: str(x))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=lambda d: jnp.dtype(d).name)
def test_decode_kernel_matches_reference(dtype, heads, dim, blocks, lengths):
    C = BLOCK * blocks
    rng = np.random.default_rng(heads * dim + blocks)
    q, k, v = (jnp.asarray(rng.normal(size=shape), dtype) for shape in
               [(SLOTS, 1, heads, dim)] + [(SLOTS, C, heads, dim)] * 2)
    lens = jnp.asarray(LENGTHS[lengths](C), jnp.int32)
    assert fa._decode_block(C, heads, dim, jnp.dtype(dtype).itemsize, BLOCK,
                            interpret=True) == BLOCK
    want = fa._decode_reference(q, k, v, lens, dim ** -0.5)
    decode = lambda *a: fa.flash_decode(*a, block_k=BLOCK)
    got = decode(q, k, v, lens)
    assert got.shape == (SLOTS, 1, heads, dim) and got.dtype == q.dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.asarray(jax.jit(decode)(q, k, v, lens)),
                                  np.asarray(got))


def _qkv(rng, dtype, C, heads=4, dim=16, slots=SLOTS):
    return [jnp.asarray(rng.normal(size=shape), dtype) for shape in
            [(slots, 1, heads, dim)] + [(slots, C, heads, dim)] * 2]


@pytest.mark.parametrize("blocks", [3, 8], ids=lambda n: f"{n}blk")
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=lambda d: jnp.dtype(d).name)
def test_dead_key_blocks_are_not_computed_on(dtype, blocks):
    """Every key block wholly past a slot's length is NaN in K and in V. A
    kernel that computed on it (as the one before did: v * 0 of a NaN) would
    return NaN; this one returns what it returns on the clean cache, which
    is the reference row's result."""
    C = BLOCK * blocks
    q, k, v = _qkv(np.random.default_rng(blocks), dtype, C)
    lengths = [1, BLOCK, BLOCK + 1, C - BLOCK]
    dead = np.zeros((SLOTS, C, 1, 1), bool)
    for s, n in enumerate(lengths):
        dead[s, -(-n // BLOCK) * BLOCK:] = True
    assert dead.any(axis=1).all()
    poisoned = [jnp.where(dead, jnp.nan, x) for x in (k, v)]
    lens = jnp.asarray(lengths, jnp.int32)
    decode = lambda k, v: fa.flash_decode(q, k, v, lens, block_k=BLOCK)
    got = np.asarray(decode(*poisoned), np.float32)
    np.testing.assert_array_equal(got, np.asarray(decode(k, v), np.float32))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        got, np.asarray(fa._decode_reference(q, k, v, lens, 16 ** -0.5),
                        np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("blocks", [1, 3, 8], ids=lambda n: f"{n}blk")
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=lambda d: jnp.dtype(d).name)
def test_skipping_dead_blocks_changes_no_bit(monkeypatch, dtype, blocks):
    """For every length >= 1 — the first token, a block's edge, one past
    it, a full slot — the kernel's output is bit for bit that of the same
    kernel with every block live (what it was before it stepped over any)."""
    C = BLOCK * blocks
    q, k, v = _qkv(np.random.default_rng(10 + blocks), dtype, C, slots=6)
    lens = jnp.asarray([1, BLOCK, min(BLOCK + 1, C), C, max(1, C - 1),
                        max(1, C // 2)], jnp.int32)
    call = lambda name: np.asarray(fa._decode_call(
        q, k, v, lens, 0.25, BLOCK, True, name), np.float32)
    got = call("flash_decode")
    # `_decode_call` is jitted: another kernel name is another trace, which
    # sees the patched rule (and leaves the real one's trace alone)
    monkeypatch.setattr(fa, "_live_blocks", lambda length, block_c, nk: nk)
    np.testing.assert_array_equal(got, call("flash_decode_all_live"))


def test_key_blocks_a_slot_reads():
    """The kernel's loop bound as a pure function of the length: the blocks
    up to the one that holds the last valid position and no other, all of
    them for a full slot, and all of them for length 0 (the uniform
    average)."""
    B, nk = 128, 8
    live = lambda n: int(fa._live_blocks(jnp.int32(n), B, nk))
    assert [live(n) for n in (1, 127, 128, 129, 3 * B, 3 * B + 1)] == \
        [1, 1, 1, 2, 3, 4]
    assert [live(n) for n in (nk * B - B + 1, nk * B, 0)] == [nk] * 3
    for n in range(1, nk * B + 1, 37):
        assert (live(n) - 1) * B < n <= live(n) * B


def test_key_block_follows_heads_dim_and_dtype():
    """Compiled: all heads of a key block in a tile of at most 2 MB, the
    block a multiple of 128 that divides the capacity; `block_k` caps it."""
    block = lambda C, H, D, size, cap=1024: fa._decode_block(
        C, H, D, size, cap, interpret=False)
    assert block(1024, 16, 64, 4) == 512          # the opt350m cell
    assert block(1024, 16, 64, 2) == 1024
    assert block(4096, 4, 64, 2) == 1024          # block_k caps it
    assert block(256, 4, 64, 2) == 256            # chip_smoke.py's decoder
    assert block(1024, 16, 64, 4, cap=256) == 256
    assert block(384, 32, 128, 4) == 128
    assert block(64, 2, 16, 4) is None            # positions under 128
    assert block(1024, 16, 12, 4) is None         # head_dim off the sublanes
    assert block(1024, 16, 8, 2) is None          # bfloat16 packs 16 sublanes


def test_decode_does_not_go_through_the_training_forward(monkeypatch):
    """flash_decode is a kernel of its own: no fold of heads, no mask built
    in HBM, nothing of `_flash` / `_flash_forward`."""
    def refuse(*a, **k):
        raise AssertionError("decode went through the training forward")
    for name in ("_flash", "_flash_forward", "_fold_heads", "_prep_mask"):
        monkeypatch.setattr(fa, name, refuse)
    q = jnp.ones((2, 1, 2, 8), jnp.float32)
    kv = jnp.ones((2, 16, 2, 8), jnp.float32)
    out = fa.flash_decode(q, kv, kv, jnp.array([3, 16]))
    np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-6)
    jaxpr = str(jax.make_jaxpr(
        lambda *a: fa.flash_decode(*a))(q, kv, kv, jnp.array([3, 16])))
    assert jaxpr.count("pallas_call") == 1 and "flash_decode" in jaxpr
