"""The append kernel of `kernels/flash_attention.py` (`kv_append`, interpret
mode on the CPU) against the vmapped `dynamic_update_slice` it replaces in the
decode step, bit for bit over the WHOLE buffer: the appended position of
every slot holds the new token's K and V, every other element is as it was.
Positions at both ends of a 128-position block and of the cache, a full slot
(the engine clamps its position to the last one), and a mixed batch. Shapes
that do not tile take the `dynamic_update_slice` and count in
`pallas_fallback_total{kernel="kv_append"}`. The compiled kernel — in place,
no `while`, no slab rewritten — is in tests/test_tpu_compile.py."""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")

SLOTS, CAPACITY, HEADS, DIM = 4, 384, 2, 16
# what the step computes from `lengths`: pos = clip(lengths, 0, C - 1)
LENGTHS = {
    "0": lambda C: [0] * SLOTS,
    "127": lambda C: [127] * SLOTS,
    "128": lambda C: [128] * SLOTS,
    "last": lambda C: [C - 1] * SLOTS,
    "full": lambda C: [C] * SLOTS,              # clamped: overwrites C - 1
    "mixed": lambda C: [0, 129, C, 255],
}


def bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def cache_and_token(dtype, seed=0, shape=(SLOTS, CAPACITY, HEADS, DIM)):
    S, C, H, D = shape
    rng = np.random.default_rng(seed)
    k, v = (jnp.asarray(rng.normal(size=shape), dtype) for _ in range(2))
    k_new, v_new = (jnp.asarray(rng.normal(size=(S, 1, H, D)), dtype)
                    for _ in range(2))
    # values a sum over zeros would not hand through: -0.0 and an infinity
    k_new = k_new.at[0, 0, 0, 0].set(-0.0).at[1, 0, 1, 3].set(-jnp.inf)
    return k, v, k_new, v_new


@pytest.mark.parametrize("lengths", LENGTHS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=lambda d: jnp.dtype(d).name)
def test_append_kernel_matches_dynamic_update_slice(dtype, lengths):
    k, v, k_new, v_new = cache_and_token(dtype)
    pos = jnp.clip(jnp.asarray(LENGTHS[lengths](CAPACITY), jnp.int32),
                   0, CAPACITY - 1)
    assert fa._append_block(CAPACITY, DIM, jnp.dtype(dtype).itemsize,
                            interpret=True) == 128
    want_k, want_v = fa._append_reference(k, v, k_new, v_new, pos)
    got_k, got_v = jax.jit(fa.kv_append)(k, v, k_new, v_new, pos)
    for got, want, old, new in ((got_k, want_k, k, k_new),
                                (got_v, want_v, v, v_new)):
        assert got.shape == old.shape and got.dtype == old.dtype
        np.testing.assert_array_equal(bits(got), bits(want))
        # said without the reference: the token at its position, K's in K
        # and V's in V, and nothing else of the buffer touched
        at = np.asarray(pos)
        np.testing.assert_array_equal(
            bits(got)[np.arange(SLOTS), at], bits(new)[:, 0])
        untouched = np.ones(old.shape, bool)
        untouched[np.arange(SLOTS), at] = False
        np.testing.assert_array_equal(bits(got)[untouched],
                                      bits(old)[untouched])
    assert not np.array_equal(bits(got_k), bits(got_v))


def test_append_is_one_kernel_for_k_and_v():
    k, v, k_new, v_new = cache_and_token(jnp.float32)
    pos = jnp.zeros((SLOTS,), jnp.int32)
    jaxpr = str(jax.make_jaxpr(fa.kv_append)(k, v, k_new, v_new, pos))
    assert jaxpr.count("pallas_call") == 1 and "kv_append" in jaxpr
    # vmapped over the slots, the dynamic_update_slice traces as a scatter
    assert "scatter" not in jaxpr
    plain = str(jax.make_jaxpr(lambda *a: fa.kv_append(
        *a, use_pallas=False))(k, v, k_new, v_new, pos))
    assert "pallas_call" not in plain and "scatter" in plain


def test_append_block_follows_capacity_dim_and_dtype():
    """Compiled: the 128 positions that hold the append position, for a
    cache the TPU stores positions-minor (head_dim < 128) whose head_dim
    fills whole sublane tiles; anything else takes XLA's update."""
    block = lambda C, D, size: fa._append_block(C, D, size, interpret=False)
    assert block(1024, 64, 4) == 128              # the opt350m cell
    assert block(256, 64, 2) == 128               # chip_smoke.py's decoder
    assert block(4096, 32, 2) == 128
    assert block(1024, 128, 4) is None            # row-major buffer
    assert block(64, 16, 4) is None               # positions under 128
    assert block(1000, 64, 4) is None             # capacity off the lanes
    assert block(1024, 12, 4) is None             # head_dim off the sublanes
    assert block(1024, 8, 2) is None              # bfloat16 packs 16 sublanes
    assert fa._append_block(96, 16, 4, interpret=True) == 96
    assert fa._append_block(96, 128, 4, interpret=True) is None


@pytest.mark.parametrize("shape,interpret", [
    ((2, 64, 2, 16), False),          # capacity under one lane block
    ((2, 256, 2, 12), False),         # head_dim off the sublane tile
    ((2, 256, 2, 128), False),        # head_dim 128: the buffer is row-major
    ((2, 32, 2, 128), True),
], ids=str)
def test_shapes_that_do_not_tile_fall_back_and_count(shape, interpret):
    from deeplearning4j_tpu.telemetry.registry import get_registry
    S, C, H, D = shape
    k, v, k_new, v_new = cache_and_token(jnp.float32, seed=1, shape=shape)
    pos = jnp.asarray([C - 1, 3], jnp.int32)
    labels = dict(kernel="kv_append", path="dynamic_update_slice",
                  shape=f"C={C},D={D},interpret={interpret}")
    counter = lambda: get_registry().get("pallas_fallback_total")
    before = counter().get(**labels) if counter() else 0
    got = fa.kv_append(k, v, k_new, v_new, pos, interpret=interpret)
    assert counter().get(**labels) == before + 1
    want = fa._append_reference(k, v, k_new, v_new, pos)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(bits(g), bits(w))
    # use_pallas=False is a choice, not a fallback: not counted
    fa.kv_append(k, v, k_new, v_new, pos, use_pallas=False,
                 interpret=interpret)
    assert counter().get(**labels) == before + 1


def test_decode_step_appends_through_the_kernel():
    """The engine's step: with `use_pallas=True` each attention layer is
    ONE kernel — the decode kernel, which appends the step's token as it
    reads (`flash_decode_append`); no `kv_append` is left in the step — and
    the cache it leaves is the cache of the same net stepped through the
    `dynamic_update_slice` (`use_pallas=False`): bit for bit in the first
    layer, whose projections are the same, and to the rounding of the
    attention between them (kernel against reference row) in the next."""
    from deeplearning4j_tpu.decode.engine import DecodeEngine
    from deeplearning4j_tpu.zoo.models import transformer_lm
    caches = {}
    for use_pallas in (True, False):
        net = transformer_lm(vocab_size=32, d_model=32, n_layers=2, n_heads=2,
                             use_pallas=use_pallas, seed=7).init()
        eng = DecodeEngine(net, slots=3, max_len=16)
        cache = eng.init_cache()
        cache["lengths"] = jnp.asarray([0, 5, 16], jnp.int32)
        ids = np.asarray([1, 2, 3], np.int32)
        args = (net.params, net.states, cache, ids, eng._greedy_step_ops,
                None)
        # the layers share one trace of the jitted call and its kernel
        jaxpr = str(jax.make_jaxpr(eng._build_step())(*args))
        assert jaxpr.count("name=_decode_append_call") == (
            2 if use_pallas else 0)
        assert jaxpr.count("name=flash_decode") == (1 if use_pallas else 0)
        assert "kv_append" not in jaxpr
        caches[use_pallas] = eng._build_step()(*args)[0]
    np.testing.assert_array_equal(np.asarray(caches[True]["lengths"]),
                                  [1, 6, 16])
    kernel, plain = (caches[p]["layers"] for p in (True, False))
    for name in ("k", "v"):
        np.testing.assert_array_equal(bits(kernel["b0_attn"][name]),
                                      bits(plain["b0_attn"][name]))
        np.testing.assert_allclose(np.asarray(kernel["b1_attn"][name]),
                                   np.asarray(plain["b1_attn"][name]),
                                   rtol=1e-5, atol=1e-6)
