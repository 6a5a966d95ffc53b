"""Drives a training cell: one net, built once, its weights placed from the
seed, driven through its first execution in set-up (that execution is what
`correct` is decided on) and then handed, the same object, to the window.

Workload parameters (workloads/<cell>.json, "train"): batch, pool_batches,
steps_per_execution, queue_size, transfer_streams, warm_executions,
trace_executions.

The window is one call of fit(). A traced run then profiles
`trace_executions` more executions, after the window and with the device
drained: the profiler records only programs launched while it is on, keeps
a quarter of a GB of host memory and most of a second of stop_trace() per
thousand device operations (a 5-step execution has 20,600 and 8,600 copies:
six of them took 42-45 GB and two minutes), and slows the input path to a
batch per 0.5 s while it is on. So the slice gives the device time of the
programs; how often they run is taken from the window, and so are the
result's `device.busy_s` / `window_s`: the slice's device-busy time a step
times the window's steps, over the window's seconds.
"""
from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from .. import observe, traffic
from ..seeds import key_of


def build_net(config):
    """The configuration's builder call, as its file states it."""
    mod, fn = config["builder"].rsplit(".", 1)
    args = dict(config["args"])
    up = config.get("updater")
    if up:
        updaters = importlib.import_module("deeplearning4j_tpu.nn.updaters")
        args["updater"] = getattr(updaters, up["class"])(**up["args"])
    return getattr(importlib.import_module(mod), fn)(**args)


def lr_at(updater_args, step):
    """The learning rate of iteration `step` under the configuration's
    schedule: a constant, or the step map `lr_schedule_map` (iteration ->
    rate from there on)."""
    lr = updater_args["learning_rate"]
    if updater_args.get("lr_policy") == "schedule":
        for start, value in sorted((int(k), v) for k, v in
                                   updater_args["lr_schedule_map"].items()):
            if step >= start:
                lr = value
    return lr


def place(net, params):
    """The benchmark's weights into the program's parameter tree, leaf for
    leaf: same names, same shapes, the program's dtypes."""
    import jax.numpy as jnp
    placed = {}
    for name, leaves in net.params.items():
        placed[name] = {}
        for k, old in leaves.items():
            new = params[name][k]
            if new.shape != old.shape:
                raise ValueError(f"{name}/{k}: reference {new.shape}, "
                                 f"program {old.shape}")
            placed[name][k] = jnp.asarray(new, old.dtype)
    net.params = placed


def leaf_norms(tree):
    """{layer/leaf: l2 norm} of a {layer: {leaf: array}} tree, on the host."""
    import jax
    import jax.numpy as jnp
    flat = {f"{n}/{k}": v for n, d in tree.items() for k, v in d.items()}
    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in t.items()})(flat)
    return {k: float(v) for k, v in norms.items()}


def worst_leaf_gap(got, want):
    """The largest |got - want| over the leaves, each measured against the
    reference's norm of that leaf or of the median leaf, whichever is larger
    (some gradients are all but zero). Returns (gap, leaf)."""
    floor = float(np.median(list(want.values())))
    gaps = {k: abs(got[k] - want[k]) / max(want[k], floor) for k in want}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def rel_diffs(got, want):
    """How far the program's tensors lie from the reference's, not how far
    their norms do: ||got - want|| / ||want|| over all leaves together, over
    the weight matrices (conv and dense W) together, and for the worst
    weight matrix. Rounding noise shows linearly here, where a gap between
    norms shows it squared — which is why the norm gaps could not tell
    bfloat16 from float8 (PERF.md section 2) and this can."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sums(a, b):
        return ({k: jnp.sum(jnp.square(a[k].astype(jnp.float32) - b[k]))
                 for k in b},
                {k: jnp.sum(jnp.square(b[k])) for k in b})
    flat = lambda t: {f"{n}/{k}": v for n, d in t.items()
                      for k, v in d.items()}
    d2, w2 = sums(flat(got), flat(want))
    d2 = {k: float(v) for k, v in d2.items()}
    w2 = {k: float(v) for k, v in w2.items()}
    W = [k for k in w2 if k.endswith("/W")]
    return {"all": (sum(d2.values()) / sum(w2.values())) ** 0.5,
            "weights": (sum(d2[k] for k in W) / sum(w2[k] for k in W)) ** 0.5,
            "worst_weight": max((d2[k] / w2[k]) ** 0.5 for k in W)}


def run(run):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.etl.device_transform import DeviceIngest
    from deeplearning4j_tpu.etl.prefetch import DevicePrefetcher
    from deeplearning4j_tpu.optimize.listeners import IterationListener
    from deeplearning4j_tpu.telemetry.cost import (ExecutableCostRegistry,
                                                   get_cost_registry,
                                                   set_cost_registry)
    from deeplearning4j_tpu.telemetry.registry import get_registry

    # the training seams attribute their programs' memory_analysis() to the
    # process-default cost plane once there is one
    set_cost_registry(ExecutableCostRegistry(get_registry()))
    cfg, p = run.config, run.cell["train"]
    ref = run.reference()
    classes, image = cfg["args"]["num_classes"], cfg["args"]["image_size"]
    K, batch = p["steps_per_execution"], p["batch"]
    momentum = cfg["updater"]["args"]["momentum"]
    lrs = [lr_at(cfg["updater"]["args"], i) for i in range(K)]
    key = key_of(run.seed)

    pool = traffic.image_pool(run.seed, p["pool_batches"], batch, image,
                              classes)
    sets = [DataSet(x, y) for x, y in pool]
    run.mark("pool_made")
    net = build_net(cfg)
    net.init()
    net.set_ingest(DeviceIngest(one_hot_labels=classes))
    params0, _ = ref.init_params(key, classes)
    place(net, params0)
    del params0

    class Scores(IterationListener):
        def __init__(self):
            self.executions, self.at = [], []

        def iteration_done(self, model, iteration):
            self.executions.append(model.last_scores)
            self.at.append(time.perf_counter())

    log = Scores()
    net.set_listeners(log)

    def fit(groups):
        it = DevicePrefetcher(groups, queue_size=p["queue_size"],
                              transfer_streams=p["transfer_streams"])
        try:
            with jax.profiler.TraceAnnotation("bench:fit"):
                net.fit(it, steps_per_execution=K)
                jax.block_until_ready(net.params)
        finally:
            it.close()

    run.mark("net_built")
    # set-up: the first execution (compiles or reads the cache), read back
    fit(traffic.TimedGroups(sets, K, max_groups=1))
    run.mark("first_execution")
    if net.last_scores is None or net.last_scores.shape != (K,):
        raise RuntimeError("fit(steps_per_execution=K) fell back to "
                           "per-batch steps")
    first_scores = np.asarray(net.last_scores, np.float32)
    prog_trace = {n: {k: jnp.copy(v) for k, v in s[0].trace.items()}
                  for n, s in net.opt_state.items() if s[0].trace}
    trace_norms = leaf_norms({n: dict(s[0].trace) for n, s in
                              net.opt_state.items() if s[0].trace})
    fresh, _ = ref.init_params(key, classes)
    delta_norms = leaf_norms({n: {k: v.astype(jnp.float32) - fresh[n][k]
                                  for k, v in d.items()}
                              for n, d in net.params.items() if d})
    del fresh
    if p.get("warm_executions", 1):
        fit(traffic.TimedGroups(sets, K, max_groups=p["warm_executions"]))
    log.executions.clear()
    log.at.clear()
    registry = get_registry()
    cost = get_cost_registry()
    setup_s = run.setup_seconds()

    # the window
    groups = traffic.TimedGroups(sets, K, seconds=run.seconds)
    before = observe.snapshot(registry)
    t0 = time.perf_counter()
    fit(groups)
    elapsed = time.perf_counter() - t0
    after = observe.snapshot(registry)
    steps = groups.served
    window_scores = np.concatenate([np.asarray(s, np.float32)
                                    for s in log.executions]) \
        if log.executions else np.zeros((0,), np.float32)
    log_at = list(log.at)
    slice_ = observe.TraceSlice(run.trace_dir, run.trace)
    if run.trace:
        with slice_:
            fit(traffic.TimedGroups(sets, K,
                                    max_groups=p["trace_executions"]))
    reduced = slice_.reduce()
    peak_bytes = observe.memory_peak_bytes(cost.table())

    # the program's state is freed; the reference follows the first execution
    net.set_listeners()
    del net, log, sets
    gc.collect()
    t_ref = time.perf_counter()
    ref_losses, ref_trace, ref_p0, ref_p = ref.follow(
        key, pool[:K], lrs, momentum, classes)
    momentum_diff = rel_diffs(prog_trace, ref_trace)
    ref_trace_norms = leaf_norms(ref_trace)
    ref_delta_norms = leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, ref_p, ref_p0))
    ref_s = time.perf_counter() - t_ref

    lim = run.cell["limits"]
    ck = run.check
    compiles = after.get("jit_compiles_total", 0) \
        - before.get("jit_compiles_total", 0)
    ck.at_most("compiles_in_window", float(compiles), 0.0)
    ck.at_most("window_scores_not_finite",
               float(np.sum(~np.isfinite(window_scores)))
               + float(len(window_scores) != steps), 0.0)
    ck.within("first_loss", float(first_scores[0]),
              np.log(classes) - 1.0, np.log(classes) + 2.5)
    loss_gap = max(abs(float(a) - b) / abs(b)
                   for a, b in zip(first_scores, ref_losses))
    ck.at_most("loss_rel_gap_first_execution", loss_gap, lim["loss_rel_gap"])
    ck.at_most("momentum_rel_diff", momentum_diff["all"],
               lim["momentum_rel_diff"])
    g, leaf = worst_leaf_gap(trace_norms, ref_trace_norms)
    ck.at_most("momentum_norm_worst_leaf_gap", g, lim["momentum_norm_gap"])
    d, dleaf = worst_leaf_gap(delta_norms, ref_delta_norms)
    ck.at_most("param_change_norm_worst_leaf_gap", d,
               lim["param_change_norm_gap"])
    if run.control:
        # the reference in the precision below the configuration's, put in
        # the program's place and read by the same comparisons
        c_losses, c_trace, c_p0, c_p = ref.follow(
            key, pool[:K], lrs, momentum, classes,
            precision=cfg["control_precision"])
        c_momentum_diff = rel_diffs(c_trace, ref_trace)
        c_trace_norms = leaf_norms(c_trace)
        c_delta_norms = leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a - b, c_p, c_p0))
        del c_trace, c_p0, c_p
        observe.note(control_losses=c_losses,
                     control_momentum_diff=c_momentum_diff)
        run.control_rows += [
            {"check": "loss_rel_gap_first_execution",
             "value": max(abs(a - b) / abs(b)
                          for a, b in zip(c_losses, ref_losses))},
            {"check": "momentum_rel_diff", "value": c_momentum_diff["all"]},
            {"check": "momentum_norm_worst_leaf_gap",
             "value": worst_leaf_gap(c_trace_norms, ref_trace_norms)[0]},
            {"check": "param_change_norm_worst_leaf_gap",
             "value": worst_leaf_gap(c_delta_norms, ref_delta_norms)[0]}]
    gaps = np.diff(np.asarray(log_at)) if len(log_at) > 1 else np.zeros(1)
    wait = after.get("etl_consumer_wait_ms") or {}
    observe.note(executions=len(log_at),
                 dispatch_gap_s={"p50": float(np.median(gaps)),
                                 "max": float(gaps.max()),
                                 "over_1s": int((gaps > 1.0).sum())},
                 drain_s=t0 + elapsed - log_at[-1] if log_at else None,
                 input_wait_ms=wait.get("sum", 0.0) - (before.get(
                     "etl_consumer_wait_ms") or {}).get("sum", 0.0))
    observe.note(momentum_diff=momentum_diff)
    observe.note(first_execution_losses=[float(s) for s in first_scores],
         reference_losses=ref_losses, worst_momentum_leaf=leaf,
         worst_param_change_leaf=dleaf, reference_seconds=ref_s,
         steps=steps, window_seconds=elapsed)

    samples = steps * batch
    chips = run.cell["chips"]
    device = None
    if reduced and reduced["programs"] and steps:
        # the loop the cell times, not the slice after it: the device-busy
        # time a step takes in the slice times the window's steps, over the
        # window (what layer_metrics/device_idle_pct.train.py reads)
        device = {"busy_s": reduced["busy_s"]
                  / (reduced["programs"][0][1] * K) * steps,
                  "window_s": elapsed}
    return {
        "attempted": steps, "failed": 0,
        "memory_peak_bytes": peak_bytes, "device": device,
        "end_to_end": {"setup_s": setup_s,
                       "samples_per_s_per_chip": samples / elapsed / chips},
        "counts": {"steps": steps, "samples": samples},
        "obs": {"before": before, "after": after, "trace": reduced,
                "cell": run.cell, "config": cfg, "peak": run.peak,
                "window": {"seconds": elapsed, "steps": steps,
                           "samples": samples, "chips": chips}},
    }
