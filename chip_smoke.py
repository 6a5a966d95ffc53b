#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py            # one TPU chip: train + serve
    python3 chip_smoke.py --chips 4  # four chips: the sharded paths only
    python3 chip_smoke.py --only kernels  # one phase: train, kernels, serve

One process, default platform (no JAX_PLATFORMS, no jax_platforms set here),
the entry points a user calls, at the full width of models the repo supports;
depth is never cut here because both models fit one 16 GB chip whole.

  train  ResNet-50 (1000 classes, 224x224, bf16 compute, Nesterovs), batch
         256, fed uint8 pixels + int32 ids through DevicePrefetcher and
         net.set_ingest: two scanned executions of 5 steps, one fit_batch.
  serve  the flash kernels against attention_reference on the chip, and the
         decode step's attention kernel on a cache in whole tiles (4 K/V
         heads of 128: a ring of 1,024 and a slab of 6,144, 48 slots, 24
         steps) against plain jax.numpy, slabs bit for bit; latent
         attention's kernels at `kimi_k27_code`'s shapes (`latent_append` +
         `mla_decode` at 64 heads on a 128 x 6,144 x 640 slab, the blockwise
         `mla_prefill` at a 4,096 bucket against the plain form); two
         fit_batch steps of the 256-wide transformer_lm at 16 x 512 tokens
         (both backward kernels inside the normal train step); then the same
         net through ModelSerializer -> ServingServer(scan_dir=...,
         decode=True) -> deploy by name -> /predict and concurrent /generate
         over HTTP, slab and paged, each stream compared with net.generate.

Any check that fails, any phase that raises, any device that is not a TPU:
a non-zero exit and no result line. Nothing is caught. With no arguments the
last line of stdout is exactly
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import tempfile
import threading
import time
import warnings

import numpy as np

# bf16 attention tolerances of tests/test_kernels.py
# (test_bf16_inputs_fwd_bwd: forward 3e-2, gradients 6e-2)
BF16_FWD_TOL = 3e-2
BF16_BWD_TOL = 6e-2
# Two engines that batch differently (the server's 8 slots, net.generate's 1)
# round differently in bf16, and greedy decoding turns a rounding difference
# into another token wherever two logits are closer than the rounding. Such a
# difference is forgiven only where BOTH tokens' log-probabilities under the
# uncached model are within this of its best: a gap is the difference of two
# logits, each good to the forward tolerance above (logits are O(1), like the
# attention outputs that tolerance was set for). On probabilities the rule
# would forgive anything — over 256 near-uniform classes every probability is
# under 2e-2. Anywhere else a differing token fails the run.
LOGIT_TIE_TOL = 2 * BF16_FWD_TOL


def require(ok, what):
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def require_first_score(score, classes):
    """An untrained softmax over `classes` scores ln(classes) plus half the
    variance of its random logits (ResNet-50's He-initialised head: about 1
    nat at 1000 classes); well under ln(classes) or far above it means the
    step did not see the labels it was given."""
    want = math.log(classes)
    require(want - 1.0 < score < want + 2.5,
            f"first score {score:.3f} is not in the band of ln({classes}) = "
            f"{want:.3f}")


def note(**fields):
    """An earlier line worth keeping (never the last)."""
    print(json.dumps(fields, default=str), flush=True)


def device_record():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


class CacheEvents:
    """Counts of JAX's persistent-compilation-cache hits and misses."""

    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1


def seam_rows(cost):
    """{executable: shadow-capture seconds, Pallas kernels in the program}
    for every row of a cost plane."""
    return {row["executable"]: {
        "capture_s": None if row["capture_ms"] is None
        else round(row["capture_ms"] / 1e3, 3),
        "pallas_kernels": row["pallas_kernels"]} for row in cost.table()}


# --------------------------------------------------------------------- train
def resnet_batches(n, batch, image, classes, seed):
    from deeplearning4j_tpu.datasets.dataset import DataSet
    rng = np.random.default_rng(seed)
    return [DataSet(rng.integers(0, 256, size=(batch, image, image, 3),
                                 dtype=np.uint8),
                    rng.integers(0, classes, batch).astype(np.int32))
            for _ in range(n)]


def make_resnet(image, classes):
    from deeplearning4j_tpu.etl.device_transform import DeviceIngest
    from deeplearning4j_tpu.nn.updaters import Nesterovs
    from deeplearning4j_tpu.zoo.models import resnet50
    net = resnet50(num_classes=classes, image_size=image,
                   updater=Nesterovs(learning_rate=0.05, momentum=0.9),
                   compute_dtype="bfloat16")
    net.init()
    net.set_ingest(DeviceIngest(one_hot_labels=classes))
    return net


def train_phase(batch=256, image=224, classes=1000, K=5, seed=0):
    """ResNet-50 through fit(DataSetIterator): the scanned K-step path fed
    by the prefetcher, then one plain fit_batch."""
    import jax
    from deeplearning4j_tpu.datasets.iterator.base import ListDataSetIterator
    from deeplearning4j_tpu.etl.prefetch import DevicePrefetcher

    from deeplearning4j_tpu.optimize.listeners import IterationListener

    class ScoreLog(IterationListener):
        """Keeps every execution's per-step scores."""

        def __init__(self):
            self.executions = []

        def iteration_done(self, model, iteration):
            scores = getattr(model, "last_scores", None)
            self.executions.append(None if scores is None
                                   else np.asarray(scores, np.float32))

    t0 = time.perf_counter()
    net = make_resnet(image, classes)
    log = ScoreLog()
    net.set_listeners(log)
    sets = resnet_batches(2 * K + 1, batch, image, classes, seed)
    it = DevicePrefetcher(ListDataSetIterator(sets[:2 * K]), queue_size=3,
                          transfer_streams=8)
    try:
        net.fit(it, steps_per_execution=K)
    finally:
        it.close()
    require(len(log.executions) == 2,
            f"expected 2 scanned executions, saw {len(log.executions)}")
    require(net.last_scores is not None and net.last_scores.shape == (K,),
            "fit(steps_per_execution=K) fell back to per-batch steps "
            f"(last_scores={getattr(net, 'last_scores', None)})")
    net.fit_batch(sets[2 * K])
    jax.block_until_ready(net.params)
    scores = np.concatenate(log.executions[:2]
                            + [np.asarray([net.score_value], np.float32)])
    require(np.all(np.isfinite(scores)), f"non-finite score in {scores}")
    require_first_score(float(scores[0]), classes)
    note(phase="train", model="resnet50", batch=batch, image=image,
         steps=len(scores), scores=[round(float(s), 4) for s in scores],
         wall_s=round(time.perf_counter() - t0, 1),
         peak_bytes_in_use=peak_bytes())


# --------------------------------------------------------------- kernel check
def kernel_phase(shape=(4, 4096, 8, 64), seed=0):
    """Compiled flash forward + both backward kernels against
    attention_reference on this device, causal, bf16."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.kernels import flash_attention
    from deeplearning4j_tpu.parallel.ring_attention import attention_reference

    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
               for _ in range(3))

    def graded(attend):
        def loss(q, k, v):
            out = attend(q, k, v, causal=True)
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    flash = graded(flash_attention)
    require_kernels(
        {"pallas_kernels": flash.lower(q, k, v).compile().as_text().count(
            "tpu_custom_call")}, "flash_attention forward + backward")
    (_, out), grads = flash(q, k, v)
    (_, ref_out), ref_grads = graded(attention_reference)(q, k, v)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    fwd = float(np.max(np.abs(f32(out) - f32(ref_out))))
    require(np.all(np.isfinite(f32(out))), "flash forward is not finite")
    require(fwd <= BF16_FWD_TOL, f"flash forward off by {fwd}")
    bwd = []
    for g, r in zip(grads, ref_grads):
        g, r = f32(g), f32(r)
        require(np.all(np.isfinite(g)), "flash gradient is not finite")
        bwd.append(float(np.max(np.abs(g - r))
                         / max(1.0, float(np.max(np.abs(r))))))
    require(max(bwd) <= BF16_BWD_TOL, f"flash backward off by {bwd}")
    # a sliding window's forward (no backward kernel knows a window)
    window = min(1024, shape[1] // 2)
    windowed = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window))
    require_kernels(
        {"pallas_kernels": windowed.lower(q, k, v).compile().as_text().count(
            "tpu_custom_call")}, "flash_attention(window=)")
    win = float(np.max(np.abs(f32(windowed(q, k, v)) - f32(
        attention_reference(q, k, v, causal=True, window=window)))))
    require(win <= BF16_FWD_TOL, f"windowed flash forward off by {win}")
    note(phase="kernels", shape=list(shape), dtype="bfloat16",
         fwd_max_abs_err=fwd, bwd_max_rel_err=bwd, window=window,
         windowed_fwd_max_abs_err=win)


# (kernel's name, ring?, slots, positions, K/V heads, head_dim): the two kinds
# of layer of `mellum2_code_decode` and `granite4hmicro_chat_decode`'s
DECODE_KERNEL_CASES = (("flash_decode_window", True, 48, 1024, 4, 128),
                       ("flash_decode", False, 48, 6144, 4, 128),
                       ("flash_decode", False, 64, 1024, 8, 64))


def decode_kernel_phase(cases=DECODE_KERNEL_CASES, heads=32, steps=24, seed=0):
    """The decode step's attention kernel on a cache declared in WHOLE TILES
    (fewer rows of 128 lanes a position than a tile's 8 sublanes — 4 K/V
    heads of 128, or 8 of 64 PACKED two to a row —: a token's rows reach
    the cache by a read-modify-write of the tile they share with another
    position), compiled, against plain jax.numpy: a sliding window's RING
    (`flash_decode_append(ring=True)`) and a full layer's slab, `steps`
    consecutive steps with the slabs donated from one to the next, as the
    engine runs them. The ring's slots start before, at and several turns
    past the wrap, a slab's at a tile's first and second position and on
    both sides of a 256-position block's edge. A slot's query heads aim, of
    each K/V head's group, at the OLDEST position the window still holds, at
    the token's tile NEIGHBOUR (position ^ 1), at the token itself, and
    nowhere: a ring one position short, a neighbour's rows lost in the tile,
    a head read from the other half of its lane row or a token that did not
    enter moves an output by ~1, which the phase shows by planting the first
    three in the reference. Both slabs afterwards bit for bit."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.kernels import flash_decode_append
    from deeplearning4j_tpu.kernels.flash_attention import (LANES, SUBLANES,
                                                            tiled_rows)

    rng = np.random.default_rng(seed)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))

    for name, is_ring, S, C, kv_heads, D in cases:
        G, scale = heads // kv_heads, float(D) ** -0.5
        aim = 2 if G >= 6 else 1    # query heads a target, of a group

        def reference(q, k, v, kn, vn, pos, short=False, neighbour_lost=False,
                      halves_swapped=False):
            """(out, k, v) on the plain [S, C, H, D] view, float32 products."""
            at = pos % C if is_ring else pos
            s_ = jnp.arange(S)
            k, v = k.at[s_, at].set(kn[:, 0]), v.at[s_, at].set(vn[:, 0])
            live = jnp.minimum(pos + 1, C)
            valid = jnp.arange(C)[None] < live[:, None]
            if short:       # the oldest position of a full ring left out
                oldest = jnp.where(live == C, (at + 1) % C, C)
                valid &= jnp.arange(C)[None] != oldest[:, None]
            ka, va = k, v
            if neighbour_lost:
                ka, va = (x.at[s_, at ^ 1].set(0) for x in (k, v))
            if halves_swapped:      # the head beside it on the lane row
                other = jnp.arange(kv_heads) ^ 1
                ka, va = ka[:, :, other], va[:, :, other]
            qf = q[:, 0].astype(jnp.float32).reshape(S, kv_heads, G, D)
            with jax.default_matmul_precision("highest"):
                sc = jnp.einsum("shgd,schd->shgc", qf,
                                ka.astype(jnp.float32)) * scale
                sc = jnp.where(valid[:, None, None], sc, -jnp.inf)
                out = jnp.einsum("shgc,schd->shgd",
                                 jax.nn.softmax(sc, axis=-1),
                                 va.astype(jnp.float32))
            return out.reshape(S, 1, heads, D), k, v

        def aimed(k, kn, pos, noise):
            """Queries [S, 1, heads, D]: of each K/V head's group `aim` at
            the oldest live key, `aim` at the tile neighbour (the token
            itself where that is not live), `aim` at the token, the rest
            noise."""
            at = pos % C if is_ring else pos
            live = jnp.minimum(pos + 1, C)
            s_ = jnp.arange(S)
            oldest = jnp.where(is_ring & (live == C), (at + 1) % C, 0)
            near = jnp.where((at ^ 1) < live, at ^ 1, at)
            k = k.at[s_, at].set(kn[:, 0])
            pick = lambda i: k[s_, i].astype(jnp.float32)     # [S, H, D]
            want = jnp.stack([pick(oldest)] * aim + [pick(near)] * aim
                             + [kn[:, 0].astype(jnp.float32)] * aim
                             + [noise[:, :, j] for j in range(G - 3 * aim)],
                             axis=2)
            return want.reshape(S, 1, heads, D).astype(jnp.bfloat16)

        tiles = tiled_rows(C, kv_heads, D)
        require(tiles, f"{kv_heads} K/V heads of {D} are not half-tile rows")
        leaf = (S, tiles, SUBLANES, max(D, LANES))
        packed = D < LANES
        k, v = (jnp.asarray(rng.normal(size=(S, C, kv_heads, D)),
                            jnp.bfloat16) for _ in range(2))
        if is_ring:     # before, at and past the wrap, and many turns on
            edge = [0, 1, C - steps - 1, C - 2, C - 1, C, C + 1, 2 * C - 1,
                    2 * C, 5 * C - 3]
            pos = np.concatenate([edge, rng.integers(0, 6 * C, S)])[:S]
        else:           # a tile's two positions, a block's edge, the end
            edge = [0, 1, C - steps, 255, 256, 256 - steps // 2]
            pos = np.concatenate([edge, rng.integers(0, C - steps, S)])[:S]
        pos = jnp.asarray(pos, jnp.int32)
        step = jax.jit(lambda q, k, v, kn, vn, pos: flash_decode_append(
            q, k, v, kn, vn, pos, ring=is_ring), donate_argnums=(1, 2))
        tk, tv = k.reshape(leaf), v.reshape(leaf)   # the kernel's slabs
        worst, program = 0.0, None
        short = lost = swapped = np.inf
        moved = lambda off, want, who: float(np.abs(
            f32(off) - f32(want))[who].max(axis=(1, 2, 3)).min())
        for _ in range(steps):
            kn, vn = (jnp.asarray(rng.normal(size=(S, 1, kv_heads, D)),
                                  jnp.bfloat16) for _ in range(2))
            noise = jnp.asarray(rng.normal(size=(S, kv_heads, G, D)),
                                jnp.float32)
            q = aimed(k, kn, pos, noise)
            if program is None:
                program = step.lower(q, tk, tv, kn, vn, pos).compile()
                require_kernels({"pallas_kernels": program.as_text().count(
                    "tpu_custom_call")}, name)
            out, tk, tv = step(q, tk, tv, kn, vn, pos)
            want, k, v = reference(q, k, v, kn, vn, pos)
            require(bool(jnp.array_equal(tk.reshape(k.shape), k))
                    and bool(jnp.array_equal(tv.reshape(v.shape), v)),
                    f"{name}: a slab is not the reference's, bit for bit")
            err = np.abs(f32(out) - f32(want))
            require(np.all(np.isfinite(f32(out))), f"{name} is not finite")
            worst = max(worst, float(err.max()))
            # the faults the aimed heads are there to show, planted in the
            # reference: each must move some output far past the tolerance
            full = np.asarray(jnp.minimum(pos + 1, C) == C)
            if is_ring and full.any():
                off = reference(q, k, v, kn, vn, pos, short=True)[0]
                short = min(short, moved(off, want, full))
            off = reference(q, k, v, kn, vn, pos, neighbour_lost=True)[0]
            seen = np.asarray((pos % C if is_ring else pos) ^ 1
                              < jnp.minimum(pos + 1, C))
            lost = min(lost, moved(off, want, seen))
            if packed:
                off = reference(q, k, v, kn, vn, pos, halves_swapped=True)[0]
                swapped = min(swapped, moved(off, want, np.ones(S, bool)))
            pos = pos + 1
        require(worst <= BF16_FWD_TOL, f"{name} off by {worst}")
        require(min(lost, short, swapped) > 10 * BF16_FWD_TOL,
                f"{name}: a planted fault moves the output by only "
                f"{min(lost, short, swapped)}")
        note(phase="decode_kernels", kernel=name, leaf=list(leaf),
             heads=[heads, kv_heads], steps=steps, out_max_abs_err=worst,
             ring_one_short_moves_a_slot_by_at_least=(
                 short if is_ring else None),
             neighbour_lost_moves_a_slot_by_at_least=lost,
             other_half_of_the_lane_row_moves_a_slot_by_at_least=(
                 swapped if packed else None))


# ------------------------------------------------- latent attention's kernels
def latent_kernel_phase(slots=128, capacity=6144, heads=64, bucket=4096,
                        steps=8, seed=0):
    """`kimi_k27_code`'s two attention paths at its own shapes, compiled,
    against plain jax.numpy in float32: the decode step's pair — the token's
    row into a `[slots, capacity, 640]` bfloat16 slab (`latent_append`,
    donated), then `mla_decode` at 64 query rows a slot over the slot's live
    rows — for `steps` consecutive steps from lengths on both sides of a
    256-position block's edge and at the slab's end, the slab afterwards bit
    for bit; and the blockwise prefill (`mla_prefill`, keys 128 | 64 against
    values 128) over a `bucket` of positions against the plain form, whose
    `[heads, T, T]` scores are formed eight heads at a time so that they fit.
    A query aimed at the oldest key, at the newest and at a key just under
    the diagonal's block edge moves by ~1 when that key is lost, which the
    phase shows by planting each loss in the reference."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.kernels import (latent_append, mla_decode,
                                            mla_prefill)

    rng = np.random.default_rng(seed)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    S, C, H, W, R = slots, capacity, heads, 640, 512
    scale = 192.0 ** -0.5 * 2.00474

    # ---- the step's pair
    slab = jnp.asarray(rng.normal(size=(S, C, W)) * 0.5, jnp.bfloat16)
    slab = slab.at[:, :, 576:].set(0)
    edge = [0, 1, 255, 256, 256 - steps // 2, C - steps - 1]
    pos = jnp.asarray(np.concatenate(
        [edge, rng.integers(0, C - steps, S)])[:S], jnp.int32)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(slab, rows, q, pos):
        slab = latent_append(slab, rows, pos)
        return slab, mla_decode(q, slab, pos + 1, rank=R)

    def reference(slab, q, lengths, lost=None):
        """Sixteen slots at a time, float32 products; `lost` [S]: a position
        a slot whose row is left out."""
        outs = []
        for a in range(0, S, 16):
            lat = slab[a:a + 16].astype(jnp.float32)
            with jax.default_matmul_precision("highest"):
                sc = jnp.einsum("shw,scw->shc",
                                q[a:a + 16].astype(jnp.float32), lat)
                valid = jnp.arange(C)[None] < lengths[a:a + 16, None]
                if lost is not None:
                    valid &= jnp.arange(C)[None] != lost[a:a + 16, None]
                p = jax.nn.softmax(jnp.where(valid[:, None], sc, -jnp.inf),
                                   axis=-1)
                outs.append(jnp.einsum("shc,scr->shr", p, lat[:, :, :R]))
        return jnp.concatenate(outs)

    program, worst, moved = None, 0.0, np.inf
    for _ in range(steps):
        rows = jnp.asarray(rng.normal(size=(S, W)) * 0.5, jnp.bfloat16)
        rows = rows.at[:, 576:].set(0)
        plain = slab.at[jnp.arange(S), pos].set(rows)
        # a third of a slot's heads at its oldest row, a third at the new
        # one, the rest noise; 6 x the row: the aimed key takes the softmax
        aimed = jnp.stack([plain[:, 0], rows], axis=1).astype(jnp.float32)
        q = jnp.concatenate(
            [jnp.repeat(aimed * 6, H // 3, axis=1)[:, :2 * (H // 3)],
             jnp.asarray(rng.normal(size=(S, H - 2 * (H // 3), W)) * 0.1,
                         jnp.float32)], axis=1).astype(jnp.bfloat16)
        if program is None:
            program = step.lower(slab, rows, q, pos).compile()
            require_kernels({"pallas_kernels": program.as_text().count(
                "tpu_custom_call")}, "latent_append + mla_decode")
        slab, out = step(slab, rows, q, pos)
        require(bool(jnp.array_equal(slab, plain)),
                "latent_append: the slab is not the reference's, bit for bit")
        want = reference(plain, q, pos + 1)
        require(np.all(np.isfinite(f32(out))), "mla_decode is not finite")
        worst = max(worst, float(np.abs(f32(out) - f32(want)).max()))
        off = reference(plain, q, pos + 1, lost=pos)      # the new row lost
        moved = min(moved, float(np.abs(f32(off) - f32(want)).max(
            axis=(1, 2)).min()))
        pos = pos + 1
    require(worst <= BF16_FWD_TOL, f"mla_decode off by {worst}")
    require(moved > 10 * BF16_FWD_TOL,
            f"mla_decode: a lost row moves the output by only {moved}")
    # the kernel alone at the lengths the steps ended on: 100 calls in one
    # program (each call's lengths wait for the call before), best of 3
    calls = 100

    @jax.jit
    def alone(q, slab, lengths):
        def body(_, n):
            out = mla_decode(q, slab, n, rank=R)
            return n + (out[0, 0, 0] > 1e30).astype(jnp.int32)
        return jax.lax.fori_loop(0, calls, body, lengths)

    jax.block_until_ready(alone(q, slab, pos))
    best = np.inf
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(alone(q, slab, pos))
        best = min(best, (time.perf_counter() - t0) / calls)
    live_blocks = int(np.sum(-(-np.asarray(pos) // 256)))
    note(phase="latent_kernels", kernel="latent_append+mla_decode",
         slab=[S, C, W], heads=H, steps=steps, out_max_abs_err=worst,
         new_row_lost_moves_a_slot_by_at_least=moved,
         mla_decode_ms_a_call=round(best * 1e3, 4),
         mla_decode_live_blocks_of_256=live_blocks,
         mla_decode_us_a_live_block=round(best * 1e6 / live_blocks, 4))

    # ---- the prefill
    T = bucket
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    qn, qp, kn, kp, v = (draw(1, T, H, 128), draw(1, T, H, 64),
                         draw(1, T, H, 128), draw(1, T, 64),
                         draw(1, T, H, 128))
    # every 97th query aims at the key 513 positions under it (across a
    # block's edge) where there is one, else at its own
    at = np.arange(T)
    target = np.where((at % 97 == 0) & (at >= 513), at - 513, at)
    qn = jnp.where((at % 97 == 0)[None, :, None, None],
                   kn[:, target] * 4, qn * 0.3)
    valid = jnp.asarray((at < T - 300)[None], jnp.float32)
    attend = jax.jit(lambda *a: mla_prefill(*a, scale=scale, key_mask=valid))
    require_kernels({"pallas_kernels": attend.lower(
        qn, qp, kn, kp, v).compile().as_text().count("tpu_custom_call")},
        "mla_prefill")
    out = attend(qn, qp, kn, kp, v)

    @jax.jit
    def plain_form(qn, qp, kn, kp, v, lost):
        with jax.default_matmul_precision("highest"):
            up = lambda a: a.astype(jnp.float32)
            s_ = (jnp.einsum("bqhn,bkhn->bhqk", up(qn), up(kn))
                  + jnp.einsum("bqhr,bkr->bhqk", up(qp), up(kp))) * scale
            keep = (at[None, :] <= at[:, None]) & (valid[0] > 0)[None, :] \
                & (at[None, :] != lost[:, None])
            p = jax.nn.softmax(jnp.where(keep[None, None], s_, -jnp.inf),
                               axis=-1)
            return jnp.einsum("bhqk,bkhv->bqhv", p, up(v))

    fwd, moved = 0.0, np.inf
    real = np.asarray(at < T - 300)
    none = jnp.full((T,), -1)
    aimed = (at % 97 == 0) & (at >= 513) & real
    for a in range(0, H, 8):
        part = lambda x: x[:, :, a:a + 8]
        want = plain_form(part(qn), part(qp), part(kn), kp, part(v), none)
        got = f32(out[:, :, a:a + 8])
        require(np.all(np.isfinite(got[:, real])),
                "mla_prefill is not finite")
        fwd = max(fwd, float(np.abs(got - f32(want))[:, real].max()))
        off = plain_form(part(qn), part(qp), part(kn), kp, part(v),
                         jnp.asarray(np.where(aimed, target, -1)))
        moved = min(moved, float(np.abs(f32(off) - f32(want))[0, aimed].max(
            axis=(1, 2)).min()))
    require(fwd <= BF16_FWD_TOL, f"mla_prefill off by {fwd}")
    require(moved > 10 * BF16_FWD_TOL,
            f"mla_prefill: a lost key moves the output by only {moved}")
    jax.block_until_ready(attend(qn, qp, kn, kp, v))
    t0 = time.perf_counter()
    for _ in range(5):
        last = attend(qn, qp, kn, kp, v)
    jax.block_until_ready(last)
    note(phase="latent_kernels", kernel=f"mla_prefill_{T}", heads=H,
         fwd_max_abs_err=fwd, aimed_key_lost_moves_a_query_by_at_least=moved,
         ms_a_call_with_its_folds=round(
             (time.perf_counter() - t0) / 5 * 1e3, 3))


# --------------------------------------------------------------------- serve
def make_lm(vocab, d_model, n_layers, n_heads):
    from deeplearning4j_tpu.zoo.models import transformer_lm
    return transformer_lm(vocab_size=vocab, d_model=d_model,
                          n_layers=n_layers, n_heads=n_heads,
                          use_pallas=True, compute_dtype="bfloat16").init()


def lm_batch(vocab, batch, seq, seed):
    import jax.numpy as jnp
    from deeplearning4j_tpu.datasets.dataset import DataSet
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, size=(batch, seq + 1))
    eye = np.eye(vocab, dtype=np.float32)
    return DataSet(jnp.asarray(eye[ids[:, :-1]]), jnp.asarray(eye[ids[:, 1:]]))


def timed_fit_batch(net, ds):
    """(score, wall seconds) of one fit_batch, the score read back inside
    the timed region."""
    t0 = time.perf_counter()
    net.fit_batch(ds)
    score = float(net.score_value)
    return score, time.perf_counter() - t0


def lm_train_steps(make, ds, vocab, events):
    """Two fit_batch steps of a transformer_lm at batch 16 x 512 — both backward
    kernels run inside the normal train step — and then the first step of a
    second, identical net: the same seam compiled again in this process,
    which nothing in memory can answer (a new jit of a new trace), so it is
    the persistent compilation cache that has to. A cache that never hits
    shows here as two equal times and fails the run."""
    net = make()
    first, cold_s = timed_fit_batch(net, ds)
    second, steady_s = timed_fit_batch(net, ds)
    require(np.all(np.isfinite([first, second])),
            f"non-finite LM score {first}, {second}")
    require_first_score(first, vocab)
    hits = events.hits
    twin_first, warm_s = timed_fit_batch(make(), ds)
    require(twin_first == first,
            f"the same seed gave another first score: {twin_first} vs {first}")
    note(compile_cache={"seam": "transformer_lm train step (first call: "
                                "trace + compile + one step)",
                        "cold_s": round(cold_s, 2), "warm_s": round(warm_s, 2),
                        "steady_step_s": round(steady_s, 4),
                        "hits_in_warm_pass": events.hits - hits,
                        "hits": events.hits, "misses": events.misses})
    require(events.hits > hits,
            "the second compile of the train step did not hit the persistent "
            "compilation cache")
    return net, [first, second]


def masked_output(model, onehot, vocab):
    """model.output on one [T, vocab] one-hot sequence, padded and masked to
    its power-of-two length bucket the way the batcher and the prefill do
    (so the shapes tile and the kernel is what answers). Returns [T, vocab]
    f32."""
    from deeplearning4j_tpu.serving.batcher import bucket_for
    T = onehot.shape[0]
    L = bucket_for(T)
    xp = np.zeros((1, L, vocab), np.float32)
    xp[0, :T] = onehot
    mask = np.zeros((1, L), np.float32)
    mask[:, :T] = 1.0
    return np.asarray(model.output(xp, mask=mask), np.float32)[0, :T]


def require_kernels(row, label):
    require(row is not None and row["pallas_kernels"],
            f"{label} was compiled without its Pallas kernel "
            f"(no tpu_custom_call in the program): {row}")


def same_stream(got, want, probs_of):
    """Token-for-token equality; a difference is forgiven only at a near-tie
    (LOGIT_TIE_TOL) of the reference distribution `probs_of(n)` at the first
    differing position n — past it the streams rightly go their own ways.
    Returns None for equal streams, else the larger of the two tokens'
    log-probability gaps to the reference's best."""
    if got == want:
        return None
    n = next((i for i, (a, b) in enumerate(zip(got, want)) if a != b),
             min(len(got), len(want)))
    require(n < min(len(got), len(want)),
            f"streams differ in length only: {got} vs {want}")
    logp = np.log(np.maximum(probs_of(n).astype(np.float64), 1e-30))
    gap = float(logp.max() - min(logp[got[n]], logp[want[n]]))
    require(gap <= LOGIT_TIE_TOL,
            f"token {n} differs ({got[n]} vs {want[n]}) and it is no near-"
            f"tie: log-probability gaps to the reference's best are "
            f"{logp.max() - logp[got[n]]:.4f} and "
            f"{logp.max() - logp[want[n]]:.4f}")
    return gap


def serve_once(zip_dir, prompts, budgets, predict_lens, vocab, slots,
               max_len, paged, seed):
    """One ServingServer life: deploy by name, warm, /predict, a concurrent
    /generate wave, and the checks on what came back."""
    from deeplearning4j_tpu.serving.server import ServingServer
    from deeplearning4j_tpu.util.http import get_json, post_json

    server = ServingServer(scan_dir=zip_dir, decode=True, decode_slots=slots,
                           decode_max_len=max_len,
                           decode_paged=paged).start()
    url = f"http://{server.host}:{server.port}"
    try:
        post_json(url + "/deploy", {"version": "lm"}, timeout=600)
        served = server.registry.get("lm").model
        engine = server.decode.engine_for(served)
        # expected streams from the restored model's own isolated runs
        solo = [served.generate(p, n) for p, n in zip(prompts, budgets)]
        for L in sorted({engine.prefill_bucket(len(p)) for p in prompts}):
            post_json(url + "/generate",
                      {"prompt": [0] * (L - 1), "max_new_tokens": 1},
                      timeout=600)

        # /predict: padded + masked length buckets through the batcher
        rng = np.random.default_rng(seed)
        eye = np.eye(vocab, dtype=np.float32)
        predict_err = []
        for T in predict_lens:
            x = eye[rng.integers(0, vocab, size=(1, T))]
            got = np.asarray(post_json(url + "/predict", {"data": x.tolist()},
                                       timeout=600)["prediction"], np.float32)
            want = masked_output(served, x[0], vocab)[None]
            require(got.shape == want.shape and np.all(np.isfinite(got)),
                    f"/predict shape {got.shape} vs {want.shape}")
            predict_err.append(float(np.max(np.abs(got - want))))
        require(max(predict_err) <= BF16_FWD_TOL,
                f"/predict differs from output(): {predict_err}")

        reg = server.metrics.registry
        compiles0 = reg.get("compiles_total").get()
        jit0 = reg.get("jit_compiles_total").get()
        results, errors = {}, []

        def fire(i):
            try:
                results[i] = post_json(
                    url + "/generate",
                    {"prompt": prompts[i], "max_new_tokens": budgets[i]},
                    timeout=600)
            except Exception as e:        # collected, required empty below
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(len(prompts))]
        for i, t in enumerate(threads):
            t.start()
            if i % 2:
                time.sleep(0.01)
        for t in threads:
            t.join(600)
        require(not errors and len(results) == len(prompts),
                f"/generate failed: {errors}")

        steady = (reg.get("compiles_total").get() - compiles0) \
            + (reg.get("jit_compiles_total").get() - jit0)
        require(steady == 0, f"{steady} steady-state recompiles")

        def ref_probs(i):
            # the uncached model's distribution for token n of request i
            return lambda n: masked_output(
                served, eye[prompts[i] + solo[i][:n]], vocab)[-1]

        ties = [same_stream(results[i]["tokens"], solo[i], ref_probs(i))
                for i in range(len(prompts))]
        near_ties = [round(g, 4) for g in ties if g is not None]
        counts = engine.executable_counts()
        require(all(v == 1 for v in counts.values()), counts)

        errs = reg.get("cost_capture_errors_total").get()
        require(errs == 0, f"cost_capture_errors_total = {errs}")
        rows = {r["executable"]: r for r in server.cost.table()}
        seams = set(counts) | {lbl for lbl in server.cost.labels()
                               if lbl.startswith("serve:")}
        require(seams <= set(rows) and any(s.startswith("serve:")
                                           for s in seams),
                f"compile seams without a cost row: {seams - set(rows)}")
        for label in counts:
            require_kernels(rows[label], label)
        metrics = get_json(url + "/metrics", timeout=60)["decode"]
        return {"paged": paged, "requests": len(prompts),
                "near_tie_logit_gaps": near_ties,
                "predict_max_abs_err": predict_err,
                "executables": counts,
                "compile_ms_total": round(
                    reg.get("compile_ms_total").get(), 1),
                "seams": seam_rows(server.cost),
                "ttft_ms_p50": metrics["ttft_ms"]["p50"],
                "itl_ms_p50": metrics["itl_ms"]["p50"]}
    finally:
        server.stop()


def serve_phase(events, vocab=256, d_model=256, n_layers=4, n_heads=4,
                batch=16, seq=512, slots=8, max_len=256,
                prompt_lens=(128, 192), n_requests=6, max_new=8, seed=0):
    from deeplearning4j_tpu.telemetry.cost import get_cost_registry
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer

    t0 = time.perf_counter()
    net, lm_scores = lm_train_steps(
        lambda: make_lm(vocab, d_model, n_layers, n_heads),
        lm_batch(vocab, batch, seq, seed), vocab, events)
    require_kernels(get_cost_registry().get("graph_train_step:std"),
                    "the transformer train step")
    rng = np.random.default_rng(seed + 1)
    lo, hi = prompt_lens
    prompts = [[int(t) for t in rng.integers(0, vocab,
                                             int(rng.integers(lo, hi + 1)))]
               for _ in range(n_requests)]
    prompts[0] = prompts[0][:lo] + [0] * max(0, lo - len(prompts[0]))
    budgets = [int(rng.integers(2, max_new + 1)) for _ in range(n_requests)]
    runs = []
    with tempfile.TemporaryDirectory() as tmp:
        ModelSerializer.write_model(net, os.path.join(tmp, "lm.zip"),
                                    save_updater=False)
        for paged in (False, True):
            runs.append(serve_once(tmp, prompts, budgets, (lo, hi), vocab,
                                   slots, max_len, paged, seed + 2))
    note(phase="serve", model="transformer_lm", d_model=d_model,
         n_layers=n_layers, lm_train_scores=[round(s, 4) for s in lm_scores],
         runs=runs, wall_s=round(time.perf_counter() - t0, 1),
         peak_bytes_in_use=peak_bytes())


# ---------------------------------------------------------------- four chips
def shard_report(tree):
    """(devices holding a shard, bytes on the fullest device, total bytes)
    over the array leaves of `tree`."""
    import jax
    per_dev, total = {}, 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if not hasattr(leaf, "addressable_shards"):
            continue
        total += leaf.size * leaf.dtype.itemsize
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                + sh.data.size * sh.data.dtype.itemsize
    return len(per_dev), max(per_dev.values(), default=0), total


def multichip_train(chips, batch=256, image=224, classes=1000, seed=0):
    """BASELINE #4 (ParallelWrapper, ZeRO-1) and a 2 x 2 data x model
    ShardedTrainer against the one-chip step on the same batch."""
    import jax
    from jax.sharding import PartitionSpec as P
    from deeplearning4j_tpu.datasets.iterator.base import ListDataSetIterator
    from deeplearning4j_tpu.parallel.parallel_wrapper import ParallelWrapper
    from deeplearning4j_tpu.parallel.sharding import (ShardedTrainer,
                                                      ShardingRules,
                                                      make_mesh)
    from deeplearning4j_tpu.parallel.zero import moment_bytes

    ds = resnet_batches(1, batch, image, classes, seed)[0]
    one = make_resnet(image, classes)
    one.fit_batch(ds)
    want = float(one.score_value)
    require(math.isfinite(want), "one-chip score is not finite")
    one_moments = moment_bytes(one.opt_state)
    del one

    net = make_resnet(image, classes)
    pw = ParallelWrapper.builder(net).workers(chips).zero(True).build()
    pw.fit(ListDataSetIterator([ds]))
    zero_score = float(net.score_value)
    n_dev, _, _ = shard_report(net.params)
    require(n_dev == chips, f"parameters sit on {n_dev} of {chips} devices")
    m_dev, m_max, _ = shard_report(
        [l for l in jax.tree_util.tree_leaves(net.opt_state)
         if getattr(l, "ndim", 0) >= 1])
    require(m_dev == chips, f"ZeRO moments sit on {m_dev} of {chips} devices")
    require(m_max <= 1.1 * one_moments / chips,
            f"ZeRO moments: {m_max} bytes on one device, replicated "
            f"{one_moments}")
    del pw, net

    net = make_resnet(image, classes)
    rules = ShardingRules()       # the rules of __graft_entry__'s dry run
    rules.add(r"^s5b\d+_c\d/W$", P(None, None, None, "model"))
    rules.add(r"^s5b\d+_proj/W$", P(None, None, None, "model"))
    rules.add(r"^s4b\d+_c\d/W$", P(None, None, None, "model"))
    rules.add(r"^out/W$", P(None, "model"))
    rules.add(r"^out/b$", P("model"))
    mesh = make_mesh(n_data=chips // 2, n_model=2)
    trainer = ShardedTrainer(net, mesh=mesh, rules=rules)
    trainer.fit_batch(ds)
    tp_score = float(net.score_value)
    p_dev, p_max, p_total = shard_report(net.params)
    require(p_dev == chips, f"parameters sit on {p_dev} of {chips} devices")
    require(p_max < 0.75 * p_total,
            f"tensor-parallel rules left {p_max} of {p_total} parameter "
            "bytes on one device")
    tol = BF16_FWD_TOL * max(1.0, abs(want))
    require(abs(zero_score - want) <= tol and abs(tp_score - want) <= tol,
            f"first-step loss: one chip {want}, ZeRO x{chips} {zero_score}, "
            f"{chips // 2}x2 mesh {tp_score}")
    note(phase="multichip_train", chips=chips, one_chip_score=want,
         zero_score=zero_score, tp_score=tp_score,
         zero_moment_bytes_per_device=m_max,
         replicated_moment_bytes=one_moments,
         tp_param_bytes_per_device=p_max, param_bytes_total=p_total)


def multichip_serve(chips, vocab=256, d_model=256, n_layers=4, n_heads=4,
                    slots=8, max_len=256, prompt_len=160, max_new=8, seed=0):
    """ServingServer(mesh=chips) against the one-device server: the same
    /predict rows and the same /generate stream."""
    from deeplearning4j_tpu.serving.server import ServingServer
    from deeplearning4j_tpu.util.http import post_json
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer

    net = make_lm(vocab, d_model, n_layers, n_heads)
    rng = np.random.default_rng(seed)
    eye = np.eye(vocab, dtype=np.float32)
    x = eye[rng.integers(0, vocab, size=(chips, prompt_len))]
    prompt = [int(t) for t in rng.integers(0, vocab, prompt_len)]
    answers = {}
    with tempfile.TemporaryDirectory() as tmp:
        ModelSerializer.write_model(net, os.path.join(tmp, "lm.zip"),
                                    save_updater=False)
        for name, mesh in (("one", None), ("mesh", chips)):
            server = ServingServer(scan_dir=tmp, decode=True,
                                   decode_slots=slots, decode_max_len=max_len,
                                   mesh=mesh).start()
            url = f"http://{server.host}:{server.port}"
            try:
                post_json(url + "/deploy", {"version": "lm"}, timeout=600)
                pred = post_json(url + "/predict", {"data": x.tolist()},
                                 timeout=600)["prediction"]
                toks = post_json(url + "/generate",
                                 {"prompt": prompt,
                                  "max_new_tokens": max_new},
                                 timeout=600)["tokens"]
                if mesh is not None:
                    require(server.mesh.chips == chips
                            and server.mesh.dispatches > 0,
                            "the mesh server did not dispatch over the mesh")
                errs = server.metrics.registry.get(
                    "cost_capture_errors_total").get()
                require(errs == 0, f"cost_capture_errors_total = {errs}")
                for label in server.decode.engine_for(
                        server.registry.get("lm").model).executable_counts():
                    require_kernels(server.cost.get(label),
                                    f"{name} server's {label}")
            finally:
                server.stop()
            answers[name] = (np.asarray(pred, np.float32), toks)
    err = float(np.max(np.abs(answers["one"][0] - answers["mesh"][0])))
    require(err <= BF16_FWD_TOL, f"mesh /predict differs by {err}")
    tie = same_stream(
        answers["mesh"][1], answers["one"][1],
        lambda n: masked_output(net, eye[prompt + answers["one"][1][:n]],
                                vocab)[-1])
    note(phase="multichip_serve", chips=chips, predict_max_abs_err=err,
         near_tie_logit_gap=tie, tokens=answers["mesh"][1])


# ---------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip paths and what they are "
                         "compared with")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", choices=("train", "kernels", "serve"),
                    help="one chip: that phase alone")
    args = ap.parse_args(argv)

    import jax
    device = device_record()
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (devices: {jax.devices()}); "
                 "this script does not fall back to the CPU")
    if device["count"] != args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs exactly that many "
                 f"devices, JAX reports {device['count']}")

    from deeplearning4j_tpu import native
    from deeplearning4j_tpu.telemetry.cost import (DONATION_MARKER,
                                                   ExecutableCostRegistry,
                                                   set_cost_registry)
    from deeplearning4j_tpu.telemetry.registry import get_registry
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    events = CacheEvents()
    # the training seams (timed_first_call) attribute to the process-default
    # cost plane, like the serving seams do to their server's
    cost = set_cost_registry(ExecutableCostRegistry(get_registry()))
    note(device=device, compile_cache_dir=cache_dir,
         io_runtime="native" if native.load() is not None else "python")

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        if args.chips == 1:
            if args.only in (None, "train"):
                train_phase(seed=args.seed)
            if args.only in (None, "kernels"):
                kernel_phase(seed=args.seed)
                decode_kernel_phase(seed=args.seed)
                latent_kernel_phase(seed=args.seed)
            if args.only in (None, "serve"):
                serve_phase(events, seed=args.seed)
        else:
            multichip_train(args.chips, seed=args.seed)
            multichip_serve(args.chips, seed=args.seed)
    donation = [str(w.message).splitlines()[0] for w in caught
                if DONATION_MARKER in str(w.message)]
    require(not donation, f"donation warnings: {donation}")

    reg = get_registry()
    fallbacks = reg.get("pallas_fallback_total")
    require(fallbacks is None or fallbacks.get() == 0,
            f"use_pallas gave way to a pure-JAX path: "
            f"{fallbacks and fallbacks.series()}")
    errs = reg.get("cost_capture_errors_total").get()
    require(errs == 0, f"cost_capture_errors_total = {errs}")
    first_calls = reg.get("jit_compile_ms_total")   # none: --only kernels
    note(compile_cache_dir=cache_dir, cache_hits=events.hits,
         cache_misses=events.misses,
         first_call_s_total=round(
             first_calls.get() / 1e3 if first_calls else 0.0, 1),
         train_seams=seam_rows(cost))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
