"""The decode kernel of `kernels/flash_attention.py` (interpret mode on the
CPU) against the masked reference row `_decode_reference`: dtypes, heads x
head_dim, a capacity of one and of several key blocks, and the lengths a
decode batch meets — a slot at its first token, a full slot, a ragged batch,
and an empty slot (no valid key: the uniform average over the cache, which
callers never read). The compiled kernel is in tests/test_tpu_compile.py."""
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
