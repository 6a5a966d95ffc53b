"""Dense / Embedding / output-layer family / AutoEncoder / RBM runtime.

Reference counterparts: nn/layers/feedforward/dense/DenseLayer.java,
feedforward/embedding/EmbeddingLayer.java, BaseOutputLayer.java, LossLayer.java,
training/CenterLossOutputLayer.java, feedforward/autoencoder/AutoEncoder.java,
feedforward/rbm/RBM.java.

Param keys follow the reference's DefaultParamInitializer ("W", "b") so the
flattened-view checkpoint layout is recognizable.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import BaseLayerModule, register_impl, apply_dropout
from ..activations import get_activation
from ..losses import get_loss
from ..weights import init_weights
from ..conf.inputs import InputType


class _DenseCore(BaseLayerModule):
    def init(self, rng, input_type, dtype=jnp.float32):
        c = self.conf
        n_in, n_out = int(c.n_in), int(c.n_out)
        k1, _ = jax.random.split(rng)
        # Kernel stored [n_in, n_out]: row-major activations @ W hits the MXU
        # directly (the reference stores [n_out, n_in] and transposes in gemm).
        params = {
            "W": init_weights(k1, (n_in, n_out), c.weight_init, fan_in=n_in,
                              fan_out=n_out, distribution=c.dist, dtype=dtype),
            "b": jnp.full((n_out,), c.bias_init or 0.0, dtype),
        }
        from ..conf.inputs import RecurrentInputType
        out_t = (InputType.recurrent(n_out)
                 if isinstance(input_type, RecurrentInputType)
                 else InputType.feed_forward(n_out))
        return params, {}, out_t

    def preoutput(self, params, x):
        # rank-3 [b, t, f] stays time-distributed (one batched gemm — beyond
        # the reference, which needs RnnToFeedForward wrapping); only rank-4
        # CNN activations flatten
        if x.ndim > 3:
            x = x.reshape(x.shape[0], -1)
        return x @ params["W"] + params["b"]

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x = apply_dropout(x, self.conf.dropout, train, rng)
        z = self.preoutput(params, x)
        return self.activation_fn()(z), state, mask


@register_impl("DenseLayer")
class DenseLayerModule(_DenseCore):
    positionwise = True


@register_impl("EmbeddingLayer")
class EmbeddingLayerModule(BaseLayerModule):
    """Index lookup: mathematically a one-hot matmul, implemented as a gather
    (reference: feedforward/embedding/EmbeddingLayer.java)."""
    positionwise = True

    def init(self, rng, input_type, dtype=jnp.float32):
        c = self.conf
        params = {"W": init_weights(rng, (int(c.n_in), int(c.n_out)), c.weight_init,
                                    fan_in=c.n_in, fan_out=c.n_out,
                                    distribution=c.dist, dtype=dtype)}
        if getattr(c, "has_bias", True):
            params["b"] = jnp.full((int(c.n_out),), c.bias_init or 0.0, dtype)
        return params, {}, InputType.feed_forward(int(c.n_out))

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        if x.ndim >= 2 and x.shape[-1] == int(self.conf.n_in) and x.shape[-1] > 1:
            idx = jnp.argmax(x, axis=-1)  # one-hot input accepted like reference
        else:
            idx = x.reshape(x.shape[0]).astype(jnp.int32)
        out = params["W"][idx]
        if "b" in params:
            out = out + params["b"]
        return self.activation_fn()(out), state, mask


class BaseOutputLayerModule(_DenseCore):
    """Dense + integrated loss (reference: BaseOutputLayer.java)."""

    def is_output_layer(self):
        return True

    def loss_fn(self):
        return get_loss(self.conf.loss)

    def score(self, params, x, labels, mask=None, train=False, rng=None):
        x = apply_dropout(x, self.conf.dropout, train, rng)
        z = self.preoutput(params, x)
        return self.loss_fn()(labels, z, self.conf.activation, mask)


@register_impl("OutputLayer")
class OutputLayerModule(BaseOutputLayerModule):
    positionwise = True


@register_impl("RnnOutputLayer")
class RnnOutputLayerModule(BaseOutputLayerModule):
    """Applies the dense projection per timestep on [b,t,f]
    (reference: nn/layers/recurrent/RnnOutputLayer.java)."""
    positionwise = True

    def preoutput(self, params, x):
        return x @ params["W"] + params["b"]

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        z = self.preoutput(params, x)
        return self.activation_fn()(z), state, mask

    def score(self, params, x, labels, mask=None, train=False, rng=None):
        z = self.preoutput(params, x)
        b, t = z.shape[0], z.shape[1]
        z2 = z.reshape(b * t, -1)
        lab2 = labels.reshape(b * t, -1)
        m2 = mask.reshape(b * t) if mask is not None else None
        return self.loss_fn()(lab2, z2, self.conf.activation, m2)


@register_impl("LMHeadLayer")
class LMHeadLayerModule(RnnOutputLayerModule):
    """softmax(x W^T / logits_scaling) with W [n_out, n_in] (the embedding's
    layout) and no bias; logits and softmax in float32."""

    def init(self, rng, input_type, dtype=jnp.float32):
        c = self.conf
        n_in, n_out = int(c.n_in), int(c.n_out)
        params = {"W": init_weights(rng, (n_out, n_in), c.weight_init,
                                    fan_in=n_in, fan_out=n_out,
                                    distribution=c.dist, dtype=dtype)}
        return params, {}, InputType.recurrent(n_out)

    def preoutput(self, params, x):
        acc = jnp.promote_types(x.dtype, jnp.float32)
        z = jnp.einsum("btf,vf->btv", x, params["W"],
                       preferred_element_type=acc)
        return z / self.conf.logits_scaling


@register_impl("GatedDenseLayer")
class GatedDenseLayerModule(BaseLayerModule):
    """(activation(g) * u) W_out with (g, u) = split(x W_in): one gemm in,
    one out, no biases."""
    positionwise = True

    def init(self, rng, input_type, dtype=jnp.float32):
        c = self.conf
        n_in, n_hidden, n_out = int(c.n_in), int(c.n_hidden), int(c.n_out)
        k1, k2 = jax.random.split(rng)
        mk = lambda k, i, o: init_weights(k, (i, o), c.weight_init, fan_in=i,
                                          fan_out=o, distribution=c.dist,
                                          dtype=dtype)
        params = {"W_in": mk(k1, n_in, 2 * n_hidden),
                  "W_out": mk(k2, n_hidden, n_out)}
        from ..conf.inputs import RecurrentInputType
        out_t = (InputType.recurrent(n_out)
                 if isinstance(input_type, RecurrentInputType)
                 else InputType.feed_forward(n_out))
        return params, {}, out_t

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x = apply_dropout(x, self.conf.dropout, train, rng)
        g, u = jnp.split(x @ params["W_in"], 2, axis=-1)
        return (self.activation_fn()(g) * u) @ params["W_out"], state, mask


@register_impl("LossLayer")
class LossLayerModule(BaseLayerModule):
    """Parameterless loss on incoming activations (reference: LossLayer.java)."""
    positionwise = True

    def init(self, rng, input_type, dtype=jnp.float32):
        return {}, {}, input_type

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return self.activation_fn()(x), state, mask

    def is_output_layer(self):
        return True

    def score(self, params, x, labels, mask=None, train=False, rng=None):
        return get_loss(self.conf.loss)(labels, x, self.conf.activation, mask)


@register_impl("CenterLossOutputLayer")
class CenterLossOutputLayerModule(BaseOutputLayerModule):
    """Softmax output + center loss (reference:
    nn/layers/training/CenterLossOutputLayer.java). Class centers live in
    layer state, updated by exponential moving average toward the masked
    feature means (the reference's alpha update), not by the optimizer."""

    def init(self, rng, input_type, dtype=jnp.float32):
        params, state, out = super().init(rng, input_type, dtype)
        state = dict(state)
        state["centers"] = jnp.zeros((int(self.conf.n_out), int(self.conf.n_in)), dtype)
        return params, state, out

    def score(self, params, x, labels, mask=None, train=False, rng=None, state=None):
        base = super().score(params, x, labels, mask, train, rng)
        centers = state["centers"] if state is not None else jnp.zeros(
            (int(self.conf.n_out), int(self.conf.n_in)), x.dtype)
        assigned = labels @ centers  # [b, n_in] center of each example's class
        center_l = 0.5 * jnp.mean(jnp.sum((x - assigned) ** 2, axis=-1))
        return base + self.conf.lambda_ * center_l

    def update_centers(self, state, x, labels):
        """EMA center update (alpha), called from the train step with
        stop_gradient'd features."""
        centers = state["centers"]
        counts = jnp.sum(labels, axis=0)[:, None] + 1.0
        sums = labels.T @ jax.lax.stop_gradient(x)
        delta = (centers * jnp.sum(labels, axis=0)[:, None] - sums) / counts
        new_centers = centers - self.conf.alpha * delta
        out = dict(state)
        out["centers"] = new_centers
        return out


@register_impl("AutoEncoder")
class AutoEncoderModule(_DenseCore):
    """Denoising autoencoder (reference: feedforward/autoencoder/AutoEncoder.java).
    Supervised forward = encoder; pretrain loss = reconstruction of corrupted
    input through tied-ish decoder (separate visible bias, shared W^T)."""

    def init(self, rng, input_type, dtype=jnp.float32):
        params, state, out = super().init(rng, input_type, dtype)
        params["vb"] = jnp.zeros((int(self.conf.n_in),), dtype)
        return params, state, out

    def is_pretrainable(self):
        return True

    def pretrain_loss(self, params, x, rng):
        c = self.conf
        if x.ndim > 2:
            x = x.reshape(x.shape[0], -1)
        corrupted = x
        if c.corruption_level and c.corruption_level > 0 and rng is not None:
            keep = jax.random.bernoulli(rng, 1.0 - c.corruption_level, x.shape)
            corrupted = jnp.where(keep, x, 0.0)
        h = self.activation_fn()(corrupted @ params["W"] + params["b"])
        recon_pre = h @ params["W"].T + params["vb"]
        loss = get_loss(c.loss)(x, recon_pre, c.activation, None)
        if c.sparsity and c.sparsity > 0:
            loss = loss + c.sparsity * jnp.mean(jnp.abs(h))
        return loss


@register_impl("RBM")
class RBMModule(_DenseCore):
    """Restricted Boltzmann machine with CD-k pretraining (reference:
    feedforward/rbm/RBM.java). Supervised forward = propup probabilities."""

    def init(self, rng, input_type, dtype=jnp.float32):
        params, state, out = super().init(rng, input_type, dtype)
        params["vb"] = jnp.zeros((int(self.conf.n_in),), dtype)
        return params, state, out

    def is_pretrainable(self):
        return True

    def _propup(self, params, v):
        pre = v @ params["W"] + params["b"]
        hu = self.conf.hidden_unit
        if hu == "binary" or hu == "softmax":
            return jax.nn.sigmoid(pre) if hu == "binary" else jax.nn.softmax(pre)
        if hu == "rectified":
            return jax.nn.relu(pre)
        return pre  # gaussian

    def _propdown(self, params, h):
        pre = h @ params["W"].T + params["vb"]
        if self.conf.visible_unit == "binary":
            return jax.nn.sigmoid(pre)
        return pre  # gaussian

    def pretrain_loss(self, params, x, rng):
        """CD-k free-energy-difference surrogate: autodiff of
        FE(data) - FE(model sample) reproduces the CD gradient; the Gibbs
        chain itself is stop-gradient'd (the TPU-friendly formulation — the
        reference hand-codes the W/vb/hb gradient from the chain ends)."""
        c = self.conf
        if x.ndim > 2:
            x = x.reshape(x.shape[0], -1)
        v0 = x
        vk = v0
        key = rng if rng is not None else jax.random.PRNGKey(0)
        for _ in range(max(1, int(c.k))):
            key, k1, k2 = jax.random.split(key, 3)
            ph = self._propup(params, vk)
            h = jax.random.bernoulli(k1, jnp.clip(ph, 0, 1)).astype(x.dtype) \
                if c.hidden_unit == "binary" else ph
            pv = self._propdown(params, h)
            vk = jax.random.bernoulli(k2, jnp.clip(pv, 0, 1)).astype(x.dtype) \
                if c.visible_unit == "binary" else pv
        vk = jax.lax.stop_gradient(vk)

        def free_energy(v):
            wx_b = v @ params["W"] + params["b"]
            vbias_term = v @ params["vb"]
            hidden_term = jnp.sum(jax.nn.softplus(wx_b), axis=-1)
            return -hidden_term - vbias_term

        return jnp.mean(free_energy(v0) - free_energy(vk))


def _note_experts(routed, held, top_k, weight_bytes):
    """What an expert layer holds, as gauges in the process registry beside
    `flash_decode_block` (set at trace time, so once per compiled program):
    `moe_experts{routed,held,top_k}` and `moe_expert_weight_bytes`, the
    bytes of one layer's held experts — what a step streams when every held
    expert gets a row."""
    from ...telemetry.registry import get_registry
    reg = get_registry()
    reg.gauge("moe_experts", "Experts an expert layer holds, of `routed` "
              "the router chooses `top_k` a token from").set(
                  held, routed=routed, held=held, top_k=top_k)
    reg.gauge("moe_expert_weight_bytes",
              "Bytes of the expert matrices one expert layer holds").set(
                  weight_bytes)


@register_impl("MixtureOfExpertsLayer")
class MixtureOfExpertsLayerModule(BaseLayerModule):
    """Routed mixture-of-experts FFN (conf: nn/conf/layers.py
    MixtureOfExpertsLayer — NEW, no reference counterpart): router in
    float32 -> top-k -> the (token, expert) pairs of the experts held,
    sorted by expert into row tiles -> rows gathered -> grouped products
    (kernels/expert_gmm.py) -> times gate, summed by token. One code path
    for `forward`, a prefill and the decode step; buffers are sized for the
    worst case (every pair held), the group sizes are data. Expert weights
    are expert-major [held, ...]. The dense all-experts einsum this layer
    once was lives on in tests/test_moe.py as its oracle."""
    positionwise = True

    def _sizes(self):
        c = self.conf
        E = int(c.n_experts)
        held = E if c.experts_held is None else int(c.experts_held)
        first = int(c.first_expert)
        if not (0 < held and 0 <= first and first + held <= E):
            raise ValueError(f"experts {first}..{first + held - 1} of {E}")
        if E % int(c.n_groups):
            raise ValueError(f"{E} experts in {c.n_groups} equal groups")
        hidden = int(c.n_hidden) if c.n_hidden is not None \
            else int(c.hidden_mult) * int(c.n_out)
        return E, held, first, hidden, min(int(c.top_k), E)

    def init(self, rng, input_type, dtype=jnp.float32):
        c = self.conf
        n_in, n_out = int(c.n_in), int(c.n_out)
        E, held, _, hidden, _ = self._sizes()
        k1, k2, k3 = jax.random.split(rng, 3)
        mk = lambda k, shape, fi, fo: init_weights(
            k, shape, c.weight_init, fan_in=fi, fan_out=fo,
            distribution=c.dist, dtype=dtype)
        up = (2 if c.gated else 1) * hidden
        params = {
            "Wg": mk(k1, (n_in, E), n_in, E),              # router
            "W1": mk(k2, (held, n_in, up), n_in, hidden),   # expert up-proj
            "W2": mk(k3, (held, hidden, n_out), hidden, n_out),
        }
        if not c.gated:
            params["b1"] = jnp.zeros((held, hidden), dtype)
            params["b2"] = jnp.zeros((held, n_out), dtype)
        if c.score_function == "sigmoid":
            params["route_bias"] = jnp.zeros((E,), dtype)   # selection only
        from ..conf.inputs import RecurrentInputType
        out_t = (InputType.recurrent(n_out)
                 if isinstance(input_type, RecurrentInputType)
                 else InputType.feed_forward(n_out))
        return params, {}, out_t

    def route(self, params, xt):
        """xt [T, f] -> (experts [T, k] int32, gates [T, k] float32), the
        router in float32. "softmax": the k largest logits and their
        softmax. "sigmoid": s = sigmoid(logits); chosen by s + route_bias —
        of `n_groups` equal groups the `topk_groups` whose two largest s +
        bias sum highest, then the k largest inside them; gates =
        routed_scaling * s / sum of the chosen s: the bias moves the choice
        and never a gate."""
        c = self.conf
        E, k = int(c.n_experts), self._sizes()[4]
        acc = jnp.promote_types(xt.dtype, jnp.float32)
        r = jnp.dot(xt.astype(acc), params["Wg"].astype(acc),
                    precision=jax.lax.Precision.HIGHEST)
        if c.score_function == "softmax":
            top, experts = jax.lax.top_k(r, k)
            return experts, jax.nn.softmax(top, axis=-1)
        if c.score_function != "sigmoid":
            raise ValueError(f"score_function {c.score_function!r}")
        s = jax.nn.sigmoid(r)
        pick = s + params["route_bias"].astype(acc)
        G = int(c.n_groups)
        keep = G if c.topk_groups is None else int(c.topk_groups)
        if keep < G:
            per = pick.reshape(-1, G, E // G)
            best = jnp.sum(jax.lax.top_k(per, 2)[0], axis=-1)     # [T, G]
            _, groups = jax.lax.top_k(best, keep)
            kept = jnp.any(groups[:, :, None] == jnp.arange(G), axis=1)
            pick = jnp.where(kept[:, :, None], per, -jnp.inf).reshape(-1, E)
        _, experts = jax.lax.top_k(pick, k)
        chosen = jnp.take_along_axis(s, experts, axis=1)
        return experts, float(c.routed_scaling) * chosen \
            / jnp.sum(chosen, axis=-1, keepdims=True)

    def layout(self, params, xt, mask=None):
        """xt [T, f] -> where every (token, expert) pair of the experts held
        goes: `here`, `gates` [T, k] (does the pair take a row — its expert
        is held and `mask` [T], where given, keeps its token; its gate),
        `row_of_pair` [T * k] (its row in the grouped product's operand),
        and of that operand `token`, `live` [rows] (the token a row holds;
        is it a pair's: `live` counts the held pairs of the tokens kept),
        `group` [rows], `tile_group`, `n_tiles` (kernels/expert_gmm.py
        `group_tiles`) and `tm`. A token the mask leaves out (a prefill's
        padding) holds no pair: no row, no tile. Rows are sized for the
        worst case, every pair held: T * k // tm + held tiles; `n_tiles`,
        the tiles that hold a row, is data, and the kernel's grid ends
        there."""
        from ...kernels.expert_gmm import group_tiles, row_tile
        _, held, first, _, k = self._sizes()
        P = xt.shape[0] * k
        tm = row_tile(P, int(self.conf.n_experts), xt.dtype.itemsize)
        experts, gates = self.route(params, xt)
        local = experts - first
        here = (local >= 0) & (local < held)
        if mask is not None:
            here &= mask[:, None]
        key = jnp.where(here, local, held).reshape(P)
        order = jnp.argsort(key, stable=True)        # sorted place -> pair
        onehot = (key[:, None] == jnp.arange(held)[None]).astype(jnp.int32)
        counts = jnp.sum(onehot, axis=0)
        # a pair's rank among its expert's pairs: its place in the stable sort
        rank = jnp.sum(onehot * (jnp.cumsum(onehot, axis=0) - 1), axis=1)
        tile_group, n_tiles, tile_start = group_tiles(counts, tm,
                                                      P // tm + held)
        first_row = tile_start * tm
        row = jnp.arange(tile_group.shape[0] * tm)
        group = tile_group[row // tm]
        at = row - first_row[group]
        start = jnp.cumsum(counts) - counts
        return {"here": here, "gates": gates, "tm": tm,
                "row_of_pair": first_row[jnp.minimum(key, held - 1)] + rank,
                "token": order[jnp.minimum(start[group] + at, P - 1)] // k,
                "live": (row // tm < n_tiles) & (at < counts[group]),
                "group": group, "tile_group": tile_group, "n_tiles": n_tiles}

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        """x [..., f] -> (the gated sum of each position's held experts,
        state, mask). `mask` [...] (a prefill's validity, a padded batch's)
        takes the positions it leaves out off the layer: they hold no pair
        (`layout`) and come out zero; the others are what they are without
        a mask, bit for bit."""
        from ...kernels.expert_gmm import expert_gmm, tile_rows
        c = self.conf
        x = apply_dropout(x, c.dropout, train, rng)
        E, held, _, _, k = self._sizes()
        W1, W2 = params["W1"], params["W2"]
        _note_experts(E, held, k, (W1.size + W2.size) * W1.dtype.itemsize)
        xt = x.reshape(-1, x.shape[-1])
        with jax.named_scope("moe_route"):
            at = self.layout(params, xt, None if mask is None
                             else mask.reshape(xt.shape[0]) != 0)
        with jax.named_scope("moe_dispatch"):
            # not zeroed where no pair lies: a padding row of a live tile
            # holds some token's row, and nothing reads its product
            # (`moe_combine` takes the rows of pairs, both products treat
            # rows apart, an unread row's cotangent is zero)
            rows = xt[at["token"]]
        with jax.named_scope("moe_experts"):
            if c.gated:
                out = expert_gmm(rows, W1, W2, at["tile_group"], at["n_tiles"],
                                 use_pallas=bool(c.use_pallas),
                                 tag="x".join(str(d) for d in x.shape[:-1]))
            else:
                sizes = tile_rows(at["tile_group"], at["n_tiles"], held,
                                  at["tm"])
                h = jax.nn.relu(jax.lax.ragged_dot(rows, W1, sizes)
                                + params["b1"][at["group"]])
                out = jax.lax.ragged_dot(h, W2, sizes) \
                    + params["b2"][at["group"]]
        with jax.named_scope("moe_combine"):
            picked = out[at["row_of_pair"]].reshape(xt.shape[0], k, -1)
            out = jnp.sum(jnp.where(at["here"][:, :, None], picked, 0)
                          * at["gates"][:, :, None], axis=1).astype(x.dtype)
        out = self.activation_fn()(out.reshape(*x.shape[:-1], -1))
        return out, state, mask
