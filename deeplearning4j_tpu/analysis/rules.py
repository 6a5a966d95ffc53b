"""GL001–GL017: the rule catalog (see RULES.md for the bug-history rationale).

Each rule is intra-file AST analysis with light import resolution: aliases
from ``import x as y`` / ``from m import n as y`` are resolved so
``np.asarray`` and ``numpy.asarray`` (or ``from jax import jit``) look the
same to a rule. Resolution is intentionally shallow — a linter trades
soundness for zero-setup speed; anything it can't prove, it stays quiet on.
"""
from __future__ import annotations

import ast
import re

from .core import Rule, import_aliases, register  # noqa: F401 (re-export)


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------


def qualname(node, aliases):
    """Resolve a Name/Attribute chain to a dotted origin, or None if the base
    name isn't an import-bound alias (i.e. probably a local variable)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    base = aliases.get(node.id)
    if base is None:
        return None
    return ".".join([base] + parts[::-1])


def call_qual(node, aliases):
    """qualname of a Call's callee (None for non-calls/unresolvable)."""
    if not isinstance(node, ast.Call):
        return None
    return qualname(node.func, aliases)


def enclosing_function(ctx, node):
    """Innermost FunctionDef/AsyncFunctionDef containing `node`, or None."""
    for anc in ctx.ancestors(node):
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return anc
    return None


def is_self_attr(node, attr=None):
    return (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name) and node.value.id == "self"
            and (attr is None or node.attr == attr))


# ---------------------------------------------------------------------------
# GL001 — raw-clock
# ---------------------------------------------------------------------------

@register
class RawClockRule(Rule):
    """time.time()/time.monotonic() outside util/time_source."""

    id = "GL001"
    name = "raw-clock"
    rationale = (
        "Deadlines/timestamps read straight from the `time` module can't be "
        "driven by ManualClock, so every timeout test sleeps real wall time "
        "(or flakes). Route wall time through util.time_source.now_s()/"
        "now_ms() and durations/deadlines through monotonic_s().")

    ALLOW = ("util/time_source.py",)
    _CLOCKS = {"time.time": "now_s()/now_ms()",
               "time.monotonic": "monotonic_s()"}

    def check(self, ctx):
        if ctx.rel_path.endswith(self.ALLOW):
            return
        aliases = ctx.aliases
        for node in ctx.nodes:
            qual = call_qual(node, aliases)
            if qual in self._CLOCKS:
                yield self.violation(
                    ctx, node,
                    f"{qual}() read outside util/time_source; use "
                    f"util.time_source.{self._CLOCKS[qual]} so ManualClock "
                    f"tests can drive this clock")


# ---------------------------------------------------------------------------
# GL002 — unsafe-json
# ---------------------------------------------------------------------------

@register
class UnsafeJsonRule(Rule):
    """json.dumps on HTTP-response/payload paths instead of dumps_safe."""

    id = "GL002"
    name = "unsafe-json"
    rationale = (
        "Raw json.dumps emits bare NaN/Infinity, which JSON.parse and every "
        "strict decoder reject — a single non-finite float 500s or corrupts "
        "an HTTP response. util.http.dumps_safe serializes strict JSON "
        "(non-finite -> null, numpy scalars via default=).")

    # the one module allowed to call json.dumps on a payload path: the strict
    # serializer itself (dumps_safe's fast path IS json.dumps)
    ALLOW = ("util/http.py",)
    # modules whose whole job is building payloads that go over HTTP (stats
    # reports are POSTed to /remoteReceive and served back by UI endpoints):
    # every json.dumps there is payload serialization
    PAYLOAD_MODULES = ("ui/stats.py",)
    # callees whose arguments are HTTP bodies/responses
    _HTTP_SINKS = {"urllib.request.Request", "Request", "send_json",
                   "post_json"}

    def check(self, ctx):
        if ctx.rel_path.endswith(self.ALLOW):
            return
        aliases = ctx.aliases
        dumps_calls = [n for n in ctx.nodes
                       if call_qual(n, aliases) == "json.dumps"]
        if not dumps_calls:
            return
        if ctx.rel_path.endswith(self.PAYLOAD_MODULES):
            for call in dumps_calls:
                yield self._flag(ctx, call, "HTTP payload module")
            return
        handler_funcs = self._response_tuple_functions(ctx)
        flagged = set()
        for call in dumps_calls:
            fn = enclosing_function(ctx, call)
            if fn is not None and fn in handler_funcs:
                flagged.add(call)
                yield self._flag(ctx, call, "route handler response")
        for call, why in self._http_sink_flows(ctx, aliases, dumps_calls):
            if call not in flagged:
                flagged.add(call)
                yield self._flag(ctx, call, why)

    def _flag(self, ctx, call, why):
        return self.violation(
            ctx, call,
            f"json.dumps on an HTTP path ({why}); use util.http.dumps_safe "
            f"(strict JSON: non-finite floats -> null)")

    @staticmethod
    def _response_tuple_functions(ctx):
        """Functions returning the (status, content_type, body) route-handler
        tuple — identified by a content-type string constant in the tuple."""
        out = set()
        for node in ctx.nodes:
            if not (isinstance(node, ast.Return)
                    and isinstance(node.value, ast.Tuple)):
                continue
            for elt in node.value.elts:
                if (isinstance(elt, ast.Constant) and isinstance(elt.value, str)
                        and elt.value.startswith(("application/json", "text/"))):
                    fn = enclosing_function(ctx, node)
                    if fn is not None:
                        out.add(fn)
                    break
        return out

    def _http_sink_flows(self, ctx, aliases, dumps_calls):
        """(dumps_call, reason) pairs where the dumps result reaches an HTTP
        sink — inline, or through one simple same-function assignment."""
        dumps_set = set(dumps_calls)
        # name -> dumps node, for `body = json.dumps(d).encode()` idioms,
        # scoped per enclosing function to avoid cross-function aliasing
        tainted = {}
        for node in ctx.nodes:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                for sub in ast.walk(node.value):
                    if sub in dumps_set:
                        fn = enclosing_function(ctx, node)
                        tainted[(fn, node.targets[0].id)] = sub
                        break
        for node in ctx.nodes:
            if not isinstance(node, ast.Call):
                continue
            qual = qualname(node.func, aliases)
            name = node.func.attr if isinstance(node.func, ast.Attribute) \
                else (node.func.id if isinstance(node.func, ast.Name) else None)
            is_sink = (qual in self._HTTP_SINKS or name in self._HTTP_SINKS
                       or (name == "write" and isinstance(node.func, ast.Attribute)
                           and isinstance(node.func.value, ast.Attribute)
                           and node.func.value.attr == "wfile"))
            if not is_sink:
                continue
            fn = enclosing_function(ctx, node)
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                for sub in ast.walk(arg):
                    if sub in dumps_set:
                        yield sub, "flows into an HTTP request/response"
                    elif isinstance(sub, ast.Name) \
                            and (fn, sub.id) in tainted:
                        yield tainted[(fn, sub.id)], \
                            f"'{sub.id}' flows into an HTTP request/response"


# ---------------------------------------------------------------------------
# GL003 — lock-guard: moved to concurrency.py, where the annotation channel
# is checked against the same inferred locksets GL018–GL020 use.
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# GL004 — jit-host-sync
# ---------------------------------------------------------------------------

@register
class JitHostSyncRule(Rule):
    """Host round-trips / trace hazards inside jit-traced functions."""

    id = "GL004"
    name = "jit-host-sync"
    rationale = (
        ".item()/.tolist()/np.asarray/float()/int()/block_until_ready inside "
        "a jit-traced function either fails at trace time (concretization "
        "error) or silently forces a device->host sync per call, serializing "
        "the dispatch queue — the classic JAX/TF trace-hazard class that "
        "large codebases gate with lint.")

    _SYNC_ATTRS = {"item", "tolist", "block_until_ready"}
    _SYNC_QUALS = {"numpy.asarray", "numpy.array", "jax.device_get"}

    def check(self, ctx):
        aliases = ctx.aliases
        seen = set()
        for fn in self._traced_functions(ctx, aliases):
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call) or id(node) in seen:
                    continue
                v = self._hazard(ctx, node, aliases, fn)
                if v is not None:
                    seen.add(id(node))
                    yield v

    def _hazard(self, ctx, node, aliases, fn):
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in self._SYNC_ATTRS:
            return self.violation(
                ctx, node,
                f".{node.func.attr}() inside jit-traced `{fn.name}` forces a "
                f"host sync or fails at trace time")
        qual = call_qual(node, aliases)
        if qual in self._SYNC_QUALS:
            return self.violation(
                ctx, node,
                f"{qual}() inside jit-traced `{fn.name}` materializes the "
                f"array on host (trace hazard)")
        if isinstance(node.func, ast.Name) and node.func.id in ("float", "int") \
                and node.args and not all(isinstance(a, ast.Constant)
                                          for a in node.args):
            return self.violation(
                ctx, node,
                f"{node.func.id}() on a traced value inside `{fn.name}` "
                f"concretizes at trace time (TracerConversionError) or "
                f"host-syncs; use jnp casts or hoist out of jit")
        return None

    @classmethod
    def is_jit_expr(cls, node, aliases):
        """`jax.jit`, `jit` (imported from jax), or partial(jax.jit, ...)."""
        if qualname(node, aliases) == "jax.jit":
            return True
        if isinstance(node, ast.Call):
            q = qualname(node.func, aliases)
            if q == "jax.jit":
                return True
            if q in ("functools.partial", "partial") and node.args \
                    and qualname(node.args[0], aliases) == "jax.jit":
                return True
        return False

    def _traced_functions(self, ctx, aliases):
        """FunctionDefs traced by jit: decorated with jax.jit/partial(jax.jit)
        or passed by name to a jax.jit(...) call anywhere in the file."""
        wrapped_names = set()
        for node in ctx.nodes:
            if isinstance(node, ast.Call) \
                    and qualname(node.func, aliases) == "jax.jit" \
                    and node.args and isinstance(node.args[0], ast.Name):
                wrapped_names.add(node.args[0].id)
        for node in ctx.nodes:
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if node.name in wrapped_names \
                    or any(self.is_jit_expr(d, aliases)
                           for d in node.decorator_list):
                yield node


# ---------------------------------------------------------------------------
# GL005 — thread-hygiene
# ---------------------------------------------------------------------------

@register
class ThreadHygieneRule(Rule):
    """Threads that outlive their owner; exceptions swallowed in workers."""

    id = "GL005"
    name = "thread-hygiene"
    rationale = (
        "A non-daemon thread that nothing joins keeps the interpreter alive "
        "after main() exits (hung test runs, zombie workers); a bare "
        "`except: pass` in a worker loop turns crashes into silent data "
        "loss. Either mark threads daemon= explicitly or join them from a "
        "close()/stop()/drain() path; worker loops must record or surface "
        "errors.")

    def check(self, ctx):
        aliases = ctx.aliases
        joined = self._joined_or_daemonized(ctx)
        for node in ctx.nodes:
            if isinstance(node, ast.Call) \
                    and qualname(node.func, aliases) == "threading.Thread" \
                    and not any(kw.arg == "daemon" for kw in node.keywords):
                target = self._assign_target(ctx, node)
                if target is None or target not in joined:
                    yield self.violation(
                        ctx, node,
                        "threading.Thread without daemon= and never joined: "
                        "pass daemon= explicitly, or join it from a "
                        "close()/stop()/drain() method")
            if isinstance(node, ast.ExceptHandler) \
                    and self._swallows_everything(node, aliases) \
                    and len(node.body) == 1 \
                    and isinstance(node.body[0], ast.Pass) \
                    and self._in_loop(ctx, node):
                yield self.violation(
                    ctx, node,
                    "`except: pass` inside a worker loop swallows every "
                    "error silently; record it (counter/log) or re-raise")

    @staticmethod
    def _swallows_everything(handler, aliases):
        if handler.type is None:
            return True
        qual = qualname(handler.type, aliases)
        name = handler.type.id if isinstance(handler.type, ast.Name) else None
        return name in ("Exception", "BaseException") \
            or qual in ("Exception", "BaseException")

    @staticmethod
    def _in_loop(ctx, node):
        for anc in ctx.ancestors(node):
            if isinstance(anc, (ast.While, ast.For)):
                return True
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return False
        return False

    def _assign_target(self, ctx, call):
        """'self.<attr>' / bare name the Thread is stored into, or None."""
        for anc in ctx.ancestors(call):
            if isinstance(anc, ast.Assign):
                t = anc.targets[0]
                if is_self_attr(t):
                    return f"self.{t.attr}"
                if isinstance(t, ast.Name):
                    return t.id
                return None
            if isinstance(anc, ast.stmt):
                return None
        return None

    @staticmethod
    def _joined_or_daemonized(ctx):
        """Targets with `<target>.join(...)` called or `.daemon = True`
        assigned anywhere in the file."""
        out = set()

        def target_of(node):
            if is_self_attr(node):
                return f"self.{node.attr}"
            if isinstance(node, ast.Name):
                return node.id
            return None

        for node in ctx.nodes:
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "join":
                t = target_of(node.func.value)
                if t:
                    out.add(t)
            if isinstance(node, ast.Assign) \
                    and isinstance(node.targets[0], ast.Attribute) \
                    and node.targets[0].attr == "daemon":
                t = target_of(node.targets[0].value)
                if t:
                    out.add(t)
        return out


# ---------------------------------------------------------------------------
# GL006 — per-call-jit
# ---------------------------------------------------------------------------

@register
class PerCallJitRule(Rule):
    """jax.jit(...) built inside a loop without a cached handle."""

    id = "GL006"
    name = "per-call-jit"
    rationale = (
        "Every jax.jit(...) call creates a FRESH wrapper with its own "
        "compilation cache — invoked per loop iteration or per request it "
        "recompiles every time (seconds per call on TPU). Hoist the jit "
        "out of the loop or store the wrapper in a keyed cache "
        "(`self._jits[key] = jax.jit(fn)` is recognized as the cache idiom).")

    def check(self, ctx):
        aliases = ctx.aliases
        for node in ctx.nodes:
            if not (isinstance(node, ast.Call)
                    and qualname(node.func, aliases) == "jax.jit"):
                continue
            if self._in_loop_directly(ctx, node) \
                    and not self._cached(ctx, node):
                yield self.violation(
                    ctx, node,
                    "jax.jit(...) constructed inside a loop recompiles on "
                    "every iteration; hoist it out or store the wrapper in "
                    "a keyed cache")

    @staticmethod
    def _in_loop_directly(ctx, node):
        """Inside a For/While of the SAME function body (a def boundary stops
        the search: code in a nested function doesn't run per iteration of
        the loop that merely defines it)."""
        for anc in ctx.ancestors(node):
            if isinstance(anc, (ast.While, ast.For)):
                return True
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                return False
        return False

    @staticmethod
    def _cached(ctx, node):
        """`cache[key] = jax.jit(...)` (possibly inside a tuple) is the
        accepted memoization idiom."""
        for anc in ctx.ancestors(node):
            if isinstance(anc, ast.Assign):
                return any(isinstance(t, ast.Subscript) for t in anc.targets)
            if isinstance(anc, ast.stmt):
                return False
        return False


# ---------------------------------------------------------------------------
# GL007 — ingest-host-widening
# ---------------------------------------------------------------------------

@register
class IngestHostWideningRule(Rule):
    """Host-side float32/float64 widening casts on the ingest hot path."""

    id = "GL007"
    name = "ingest-host-widening"
    rationale = (
        "A host-side astype(np.float32)/np.asarray(..., np.float32) in a "
        "prefetcher/pipeline worker loop quadruples the bytes every batch "
        "drags across the host link, and the worker's cast and transfer "
        "are what the train loop waits for when the input path falls "
        "behind. Ship narrow bytes (uint8/int codes) and let the compiled "
        "step do the widening on-device (etl.device_transform.DeviceIngest "
        "/ network.set_ingest); a deliberate host-path remainder belongs in "
        "the baseline with a note.")

    # the ingest hot path: everything running per-batch in these modules is
    # on (or feeding) a prefetcher/pipeline worker loop
    HOT_MODULES = ("etl/prefetch.py", "etl/pipeline.py")
    # elsewhere, only functions that self-identify as worker loops
    _WORKER_FN = re.compile(r"^(_?worker\w*|\w*_loop|_process|_put)$")
    _WIDE_QUALS = {"numpy.float32", "numpy.float64"}

    def check(self, ctx):
        aliases = ctx.aliases
        hot_module = ctx.rel_path.endswith(self.HOT_MODULES)
        for node in ctx.nodes:
            if not isinstance(node, ast.Call):
                continue
            fn = enclosing_function(ctx, node)
            if fn is None:       # module-level constant setup: not per-batch
                continue
            if not hot_module and not self._WORKER_FN.match(fn.name):
                continue
            wide = self._widening(node, aliases)
            if wide is not None:
                yield self.violation(
                    ctx, node,
                    f"host-side widening cast to {wide} on the ingest hot "
                    f"path (`{fn.name}`): ship narrow bytes and cast on "
                    f"device (etl.device_transform), or baseline with a "
                    f"note if the wide host path is intentional")

    def _widening(self, node, aliases):
        """The float32/float64 target of an astype/asarray/array widening
        call, or None."""
        if isinstance(node.func, ast.Attribute) and node.func.attr == "astype":
            cand = node.args[0] if node.args else next(
                (kw.value for kw in node.keywords if kw.arg == "dtype"), None)
            return self._float_dtype(cand, aliases)
        qual = call_qual(node, aliases)
        if qual in ("numpy.asarray", "numpy.array"):
            cand = node.args[1] if len(node.args) > 1 else next(
                (kw.value for kw in node.keywords if kw.arg == "dtype"), None)
            return self._float_dtype(cand, aliases)
        return None

    def _float_dtype(self, node, aliases):
        if node is None:
            return None
        qual = qualname(node, aliases)
        if qual in self._WIDE_QUALS:
            return qual
        if isinstance(node, ast.Constant) and node.value in ("float32",
                                                             "float64"):
            return node.value
        return None


# ---------------------------------------------------------------------------
# GL008 — raw-http-client
# ---------------------------------------------------------------------------

@register
class RawHttpClientRule(Rule):
    """Outbound urllib.request / http.client use outside util/http.py."""

    id = "GL008"
    name = "raw-http-client"
    rationale = (
        "util.http.post_json/get_json are THE outbound HTTP choke point: "
        "they inject the W3C traceparent header (telemetry.propagation), so "
        "every cross-process hop joins the caller's trace, and they "
        "serialize strict JSON. A raw urllib.request/http.client call "
        "bypasses both — the request becomes an untraceable hole in the "
        "fleet view. A deliberate raw client (bulk artifact download) "
        "belongs in the baseline with a note.")

    ALLOW = ("util/http.py",)
    _CLIENT_PREFIXES = ("urllib.request.", "http.client.")

    def check(self, ctx):
        if ctx.rel_path.endswith(self.ALLOW):
            return
        aliases = ctx.aliases
        for node in ctx.nodes:
            qual = call_qual(node, aliases)
            if qual is not None and qual.startswith(self._CLIENT_PREFIXES):
                yield self.violation(
                    ctx, node,
                    f"{qual}() outside util/http.py bypasses the traceparent-"
                    f"injecting client choke point; use util.http.post_json/"
                    f"get_json (or baseline a deliberate raw client with a "
                    f"note)")


# ---------------------------------------------------------------------------
# GL009 — raw-retry-loop
# ---------------------------------------------------------------------------

@register
class RawRetryLoopRule(Rule):
    """Ad-hoc for/while retry loops with in-loop sleeps outside resilience/."""

    id = "GL009"
    name = "raw-retry-loop"
    rationale = (
        "A hand-rolled `for attempt in range(n): try ... except: "
        "time.sleep(...)` loop has no jitter (retries synchronize into "
        "thundering herds), no retry budget (a fleet-wide outage is "
        "amplified by the retry factor), no deadline (the caller waits the "
        "full worst case), and its own bespoke backoff constants. "
        "resilience.RetryPolicy is the one implementation with all four; "
        "the repo had grown three divergent copies of this loop before it "
        "existed. Sleeps that merely pace a loop (no except handler) are "
        "not retries and stay quiet.")

    # the policy implementation itself (and its chaos harness) may sleep
    ALLOW_DIR = "deeplearning4j_tpu/resilience/"

    def check(self, ctx):
        if ctx.rel_path.startswith(self.ALLOW_DIR):
            return
        aliases = ctx.aliases
        for node in ctx.nodes:
            if call_qual(node, aliases) != "time.sleep":
                continue
            if self._sleep_in_loop_handler(ctx, node):
                yield self.violation(
                    ctx, node,
                    "sleep inside an except handler inside a loop — a "
                    "hand-rolled retry; use resilience.RetryPolicy "
                    "(backoff + jitter + budget + deadline) instead")

    @staticmethod
    def _sleep_in_loop_handler(ctx, node):
        """The retry tell: the sleep sits INSIDE an except handler that is
        itself inside a for/while in the same function — the shape of all
        three hand-rolled loops this rule was derived from. A pacing sleep
        in a loop that merely CONTAINS an unrelated try/except (queue
        pollers draining with `except Empty: pass`, loops defining
        callbacks with their own handlers) stays quiet. A def/lambda
        boundary stops the search, like GL006/GL007."""
        handler = False
        for anc in ctx.ancestors(node):
            if isinstance(anc, ast.ExceptHandler):
                handler = True
            elif isinstance(anc, (ast.While, ast.For)):
                return handler
            elif isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                return False
        return False


# ---------------------------------------------------------------------------
# GL010 — jit-missing-donation
# ---------------------------------------------------------------------------

@register
class JitMissingDonationRule(Rule):
    """jax.jit over a params/opt_state-taking step without donate_argnums."""

    id = "GL010"
    name = "jit-missing-donation"
    rationale = (
        "A train step reads and writes its whole state every step: "
        "without donate_argnums the XLA "
        "executable allocates FRESH output buffers for params and updater "
        "state every step — double the state bytes resident and an extra "
        "full copy of HBM traffic, i.e. milliseconds per step. Every "
        "train-step jit in the nn/ and parallel/ hot modules must donate "
        "its params/opt_state arguments (the functional analog of the "
        "reference's in-place flattened param view). Inference jits that "
        "take `params` but must NOT donate them (the same buffers serve "
        "every call) are deliberate remainders — baseline them with a "
        "note.")

    HOT_DIRS = ("deeplearning4j_tpu/nn/", "deeplearning4j_tpu/parallel/")
    STATE_ARGS = frozenset({"params", "opt_state"})

    def check(self, ctx):
        if not ctx.rel_path.startswith(self.HOT_DIRS):
            return
        aliases = ctx.aliases
        defs = {}
        for node in ctx.nodes:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, node)
        for node in ctx.nodes:
            # call form: jax.jit(step_fn, ...) — resolve a Name argument to
            # its def in this file (the repo idiom: def then jit) or an
            # inline lambda; opaque expressions stay quiet (shallow-and-
            # sound-enough, like every rule here)
            if isinstance(node, ast.Call) \
                    and qualname(node.func, aliases) == "jax.jit" \
                    and not self._donates(node):
                target = node.args[0] if node.args else None
                fn = None
                if isinstance(target, ast.Name):
                    fn = defs.get(target.id)
                elif isinstance(target, ast.Lambda):
                    fn = target
                if fn is not None and self._takes_state(fn):
                    yield self.violation(
                        ctx, node,
                        "jax.jit over a params/opt_state-taking function "
                        "without donate_argnums: the step pays a fresh "
                        "state-sized allocation + copy every call; donate "
                        "the state args (or baseline an inference jit "
                        "with a note)")
            # decorator form: @jax.jit above a params/opt_state-taking def
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if qualname(dec, aliases) == "jax.jit" \
                            and self._takes_state(node):
                        yield self.violation(
                            ctx, node,
                            f"@jax.jit on `{node.name}({', '.join(a.arg for a in node.args.args)})` "
                            "cannot pass donate_argnums: use "
                            "jax.jit(fn, donate_argnums=...) so the "
                            "params/opt_state buffers alias in place")

    @staticmethod
    def _donates(call):
        return any(kw.arg == "donate_argnums" for kw in call.keywords)

    @classmethod
    def _takes_state(cls, fn):
        names = {a.arg for a in fn.args.args + fn.args.kwonlyargs}
        return bool(names & cls.STATE_ARGS)


# ---------------------------------------------------------------------------
# GL011 — decode-dynamic-shape
# ---------------------------------------------------------------------------

@register
class DecodeDynamicShapeRule(Rule):
    """Token-count-dependent shapes in decode/generate loops."""

    id = "GL011"
    name = "decode-dynamic-shape"
    rationale = (
        "An autoregressive decode loop that grows a tensor per token "
        "(jnp.concatenate/append of the sequence-so-far) or derives a "
        "shape from a python-int len() of the tokens-so-far presents XLA "
        "with a NEW shape every token — one full executable compile per "
        "generated token, orders of magnitude over the dispatch cost (the "
        "Julia-TPU paper's central observation, and the exact failure mode "
        "the decode engine's fixed-shape KV cache + dynamic_update_slice "
        "exists to prevent). In a decode-loop-named function, grow a "
        "FIXED-capacity buffer with lax.dynamic_update_slice and mask by a "
        "length vector instead.")

    # functions (any enclosing def) whose name marks a decode/token loop
    NAME_RE = re.compile(r"decode|generate|autoregress|token_loop",
                         re.IGNORECASE)
    GROW_CALLS = frozenset({
        "numpy.concatenate", "numpy.append", "numpy.hstack", "numpy.vstack",
        "jax.numpy.concatenate", "jax.numpy.append", "jax.numpy.hstack",
        "jax.numpy.vstack"})
    SHAPE_CTORS = frozenset({
        "numpy.zeros", "numpy.ones", "numpy.full", "numpy.empty",
        "numpy.arange", "jax.numpy.zeros", "jax.numpy.ones",
        "jax.numpy.full", "jax.numpy.empty", "jax.numpy.arange",
        "jax.nn.one_hot"})

    def check(self, ctx):
        aliases = ctx.aliases
        for node in ctx.nodes:
            if not isinstance(node, ast.Call):
                continue
            if not self._in_decode_loop(ctx, node):
                continue
            qual = qualname(node.func, aliases)
            if qual in self.GROW_CALLS:
                yield self.violation(
                    ctx, node,
                    f"{qual.split('.')[-1]} inside a decode loop grows the "
                    "sequence tensor per token — a fresh shape (and XLA "
                    "compile) every step; append into a fixed-capacity "
                    "cache with lax.dynamic_update_slice + a length mask")
            elif qual in self.SHAPE_CTORS and self._len_arg(node):
                yield self.violation(
                    ctx, node,
                    f"{qual.split('.')[-1]} sized by len(...) inside a "
                    "decode loop — a python-int shape that tracks the "
                    "token count recompiles every step; size by the fixed "
                    "cache capacity and mask the tail")

    @classmethod
    def _in_decode_loop(cls, ctx, node):
        """Inside a for/while that is itself inside (or equal to the body
        of) a def whose name matches NAME_RE. The loop requirement keeps
        one-shot setup concat (building the prompt) quiet; the name
        requirement keeps ordinary data plumbing quiet."""
        in_loop = False
        for anc in ctx.ancestors(node):
            if isinstance(anc, (ast.For, ast.While)):
                in_loop = True
            elif isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if in_loop and cls.NAME_RE.search(anc.name):
                    return True
                # keep walking: a helper defined inside a decode fn whose
                # OWN name doesn't match is still that decode loop's body
        return False

    @staticmethod
    def _len_arg(call):
        """Any argument expression containing a len(...) call."""
        for arg in list(call.args) + [kw.value for kw in call.keywords]:
            for sub in ast.walk(arg):
                if isinstance(sub, ast.Call) \
                        and isinstance(sub.func, ast.Name) \
                        and sub.func.id == "len":
                    return True
        return False


# ---------------------------------------------------------------------------
# GL012 — unbounded-spawn
# ---------------------------------------------------------------------------

@register
class UnboundedSpawnRule(Rule):
    """Thread/process spawn inside a while loop without a max-count guard."""

    id = "GL012"
    name = "unbounded-spawn"
    rationale = (
        "The elastic subsystem makes replica/thread spawning a routine "
        "reaction to load signals — and a reaction loop with no ceiling is "
        "how a flapping signal (or a health probe that never goes green) "
        "forks servers until the host dies. Spawn authority therefore "
        "lives behind the ReplicaLauncher SPI (elastic/launcher.py), which "
        "enforces max_replicas at the one choke point. Everywhere else, a "
        "threading.Thread/subprocess.Popen constructed inside a `while` "
        "loop — the unbounded-iteration shape — must sit in a function "
        "that visibly bounds the count (a comparison against a "
        "max/cap/limit/capacity name, or a non-blocking Semaphore "
        "acquire). For-loop spawns over a materialized collection "
        "(_fan_out, pipeline worker pools) are bounded by construction "
        "and stay quiet.")

    SPAWN_CALLS = frozenset({"threading.Thread", "subprocess.Popen",
                             "multiprocessing.Process"})
    #: the launcher/controller modules that OWN spawn (and its guard)
    ALLOWED_FILES = ("deeplearning4j_tpu/elastic/launcher.py",)
    GUARD_RE = re.compile(r"max|cap(?:acity)?|limit|budget|bound",
                          re.IGNORECASE)

    def check(self, ctx):
        if ctx.rel_path in self.ALLOWED_FILES:
            return
        aliases = ctx.aliases
        for node in ctx.nodes:
            if not isinstance(node, ast.Call):
                continue
            if qualname(node.func, aliases) not in self.SPAWN_CALLS:
                continue
            fn = self._enclosing_while_fn(ctx, node)
            if fn is None:
                continue
            if self._has_count_guard(fn):
                continue
            yield self.violation(
                ctx, node,
                "thread/process spawn inside a while loop with no visible "
                "max-count guard: a wedged condition forks until the host "
                "dies; bound it (compare against a max_*/cap/limit, or a "
                "non-blocking Semaphore.acquire) or route the spawn "
                "through the elastic ReplicaLauncher SPI")

    @staticmethod
    def _enclosing_while_fn(ctx, node):
        """The enclosing function def IF the spawn sits inside a `while`
        loop within it (the innermost def wins: a bounded helper defined
        inside an unbounded loop is judged on its own body)."""
        in_while = False
        for anc in ctx.ancestors(node):
            if isinstance(anc, ast.While):
                in_while = True
            elif isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return anc if in_while else None
        return None

    @classmethod
    def _has_count_guard(cls, fn):
        """A visible bound anywhere in the enclosing function: a comparison
        touching a max/cap/limit-named name or attribute, or a
        `sem.acquire(blocking=False)` try-acquire."""
        for node in ast.walk(fn):
            if isinstance(node, ast.Compare):
                for side in [node.left] + list(node.comparators):
                    for sub in ast.walk(side):
                        name = None
                        if isinstance(sub, ast.Name):
                            name = sub.id
                        elif isinstance(sub, ast.Attribute):
                            name = sub.attr
                        if name is not None and cls.GUARD_RE.search(name):
                            return True
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "acquire":
                for kw in node.keywords:
                    if kw.arg == "blocking" \
                            and isinstance(kw.value, ast.Constant) \
                            and kw.value.value is False:
                        return True
        return False


# ---------------------------------------------------------------------------
# GL013 — non-durable-publish
# ---------------------------------------------------------------------------

@register
class NonDurablePublishRule(Rule):
    """Bare os.replace publishing a persistent artifact outside util/fs.py."""

    id = "GL013"
    name = "non-durable-publish"
    rationale = (
        "os.replace is atomic in the NAMESPACE but not durable: POSIX only "
        "promises the rename survives a crash if the file's data was "
        "fsync'd before it and the parent directory's entry after it. "
        "Without both, a power loss can publish a name pointing at "
        "zero-length or stale data — the crash-after-replace bug that "
        "turned 'the newest checkpoint' into a torn zip. util.fs "
        "(atomic_write / publish_file / atomic_publish_dir) does the fsync "
        "dance once, correctly, and feeds the disk-fault chaos seam; a "
        "deliberately non-durable replace (scratch/cache-only files) "
        "belongs in the baseline with a note.")

    ALLOW = ("util/fs.py",)

    def check(self, ctx):
        if ctx.rel_path.endswith(self.ALLOW):
            return
        aliases = ctx.aliases
        for node in ctx.nodes:
            if call_qual(node, aliases) == "os.replace":
                yield self.violation(
                    ctx, node,
                    "os.replace publishes without the fsync-before/after "
                    "dance (not durable across power loss); route the "
                    "publish through util.fs.atomic_write / publish_file / "
                    "atomic_publish_dir, or baseline a deliberately "
                    "non-durable replace with a note")


# ---------------------------------------------------------------------------
# GL014 — quant-silent-widening
# ---------------------------------------------------------------------------

@register
class QuantSilentWideningRule(Rule):
    """float32/float64 widening of quantized moment/weight leaves outside
    the designated quant/dequant modules."""

    id = "GL014"
    name = "quant-silent-widening"
    rationale = (
        "The bytes diet (ROADMAP item 3) only works while the quantized "
        "leaves STAY narrow: an `astype(np.float32)` / `jnp.float32(...)` "
        "on moment or weight-quant leaves outside nn/quant.py or "
        "parallel/zero.py silently re-materializes the f32 bytes the diet "
        "removed (the step reads and writes them wide again) AND bypasses "
        "the codec's exact-round-trip contract — a hand-widened moment "
        "re-quantizes through a different path and the bitwise re-shard "
        "guarantees quietly rot. Decode through the codec (MomentCodec."
        "decode / WeightQuant.dequant), or baseline a deliberate host-side "
        "widening with a note.")

    # the designated quant/dequant homes: the codecs themselves and the
    # ZeRO layout that drives them
    ALLOW = ("nn/quant.py", "parallel/zero.py")
    # receivers/arguments that look like quantized artifacts — exact
    # segment tokens only ("quantile"/"quantity" must NOT match)
    _QUANT_NAME = re.compile(
        r"(^|_)(q8|q?codes?|q?scales?|quant|quantized|dequant|dequantized"
        r"|moments?|mu|nu)(_|$)")
    _WIDE_QUALS = {"numpy.float32", "numpy.float64",
                   "jax.numpy.float32", "jax.numpy.float64"}

    def check(self, ctx):
        if ctx.rel_path.endswith(self.ALLOW):
            return
        aliases = ctx.aliases
        for node in ctx.nodes:
            if not isinstance(node, ast.Call):
                continue
            wide, target = self._widening(node, aliases)
            if wide is None or target is None:
                continue
            name = self._leaf_name(target)
            if name is not None and self._QUANT_NAME.search(name):
                yield self.violation(
                    ctx, node,
                    f"widening `{name}` to {wide} outside the designated "
                    f"quant modules re-materializes the bytes the diet "
                    f"removed and bypasses the codec round-trip; decode "
                    f"via nn.quant (MomentCodec.decode / WeightQuant."
                    f"dequant), or baseline a deliberate widening with a "
                    f"note")

    def _widening(self, node, aliases):
        """(widened-to dtype, the node being widened), or (None, None)."""
        # x.astype(np.float32) / x.astype(dtype=np.float32) / x.astype("float32")
        if isinstance(node.func, ast.Attribute) and node.func.attr == "astype":
            cand = node.args[0] if node.args else next(
                (kw.value for kw in node.keywords if kw.arg == "dtype"), None)
            return self._float_dtype(cand, aliases), node.func.value
        qual = call_qual(node, aliases)
        # jnp.float32(x) / np.float64(x) constructor-style widening
        if qual in self._WIDE_QUALS and node.args:
            return qual, node.args[0]
        # np.asarray(x, np.float32) / jnp.array(x, dtype=jnp.float32)
        if qual in ("numpy.asarray", "numpy.array",
                    "jax.numpy.asarray", "jax.numpy.array") and node.args:
            cand = node.args[1] if len(node.args) > 1 else next(
                (kw.value for kw in node.keywords if kw.arg == "dtype"), None)
            return self._float_dtype(cand, aliases), node.args[0]
        return None, None

    def _float_dtype(self, node, aliases):
        if node is None:
            return None
        qual = qualname(node, aliases)
        if qual in self._WIDE_QUALS:
            return qual
        if isinstance(node, ast.Constant) and node.value in ("float32",
                                                             "float64"):
            return node.value
        return None

    @staticmethod
    def _leaf_name(node):
        """The identifier a widening targets: bare name, attribute tail
        (self._mu -> "_mu"), or a constant-string subscript key
        (state["qcodes"] -> "qcodes"). Calls/expressions stay None — the
        rule only claims what it can name."""
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, ast.Attribute):
            return node.attr
        if isinstance(node, ast.Subscript) \
                and isinstance(node.slice, ast.Constant) \
                and isinstance(node.slice.value, str):
            return node.slice.value
        return None


# ---------------------------------------------------------------------------
# GL015 — mesh-replicated-dispatch
# ---------------------------------------------------------------------------

@register
class MeshReplicatedDispatchRule(Rule):
    """Batch placement in serving/decode hot paths without a sharding."""

    id = "GL015"
    name = "mesh-replicated-dispatch"
    rationale = (
        "Mesh-sharded serving (ROADMAP item 1, serving/mesh.py) only "
        "splits a /predict wave across chips if the batch is PLACED with a "
        "NamedSharding before the jitted forward: a bare jax.device_put "
        "(or an implicit jnp.asarray placement) in a serving/decode "
        "dispatch path commits the whole batch to device 0, XLA compiles "
        "a replicated executable, and N-1 chips idle while reporting a "
        "healthy mesh — throughput silently collapses to single-chip with "
        "no error anywhere. In serving/ and decode/ hot paths, every "
        "device placement of a batch-shaped operand must flow through a "
        "NamedSharding / with_sharding_constraint / *_sharding helper (or "
        "sit in a visibly sharding-aware statement).")

    #: the modules whose dispatch paths feed mesh executables
    HOT_PREFIXES = ("deeplearning4j_tpu/serving/",
                    "deeplearning4j_tpu/decode/")
    #: functions that ARE the dispatch hot path (batcher dispatch, model
    #: forward, decode legs) — implicit placement only matters where the
    #: batch meets the executable
    HOT_FN_RE = re.compile(
        r"dispatch|output|predict|prefill|step|generate|warmup",
        re.IGNORECASE)
    _PLACERS = ("jax.device_put",)
    _IMPLICIT = ("jax.numpy.asarray", "jax.numpy.array", "jax.numpy.stack")
    _SHARDY = re.compile(r"shard", re.IGNORECASE)

    def check(self, ctx):
        if not ctx.rel_path.startswith(self.HOT_PREFIXES):
            return
        aliases = ctx.aliases
        for node in ctx.nodes:
            qual = call_qual(node, aliases)
            if qual in self._PLACERS:
                if not self._sharding_aware(self._statement(ctx, node)):
                    yield self.violation(
                        ctx, node,
                        "device_put without a NamedSharding in a "
                        "serving/decode hot path commits the operand to one "
                        "device — the mesh executable replicates and N-1 "
                        "chips idle; place through mesh.batch_sharding / "
                        "cache_sharding (or an explicit NamedSharding)")
            elif qual in self._IMPLICIT:
                fn = enclosing_function(ctx, node)
                if fn is not None and self.HOT_FN_RE.search(fn.name) \
                        and not self._sharding_aware(fn):
                    yield self.violation(
                        ctx, node,
                        f"{qual.split('.')[-1]} in dispatch hot path "
                        f"`{fn.name}` places the batch implicitly on device "
                        "0 with no sharding anywhere in the function; "
                        "np.asarray on the host side, then device_put under "
                        "the mesh batch sharding")

    @staticmethod
    def _statement(ctx, node):
        """Nearest enclosing statement — the visibility scope for 'is this
        placement sharding-aware': `tree_map(lambda l, s: device_put(l, s),
        cache, self.cache_shardings())` is aware through its sibling arg."""
        for anc in ctx.ancestors(node):
            if isinstance(anc, ast.stmt):
                return anc
        return node

    @classmethod
    def _sharding_aware(cls, tree):
        """Any identifier/attribute/arg name containing 'shard' in the
        subtree (NamedSharding, with_sharding_constraint, batch_sharding,
        even_sharding, pshard, out_shardings=...)."""
        if tree is None:
            return False
        for sub in ast.walk(tree):
            if isinstance(sub, ast.Name) and cls._SHARDY.search(sub.id):
                return True
            if isinstance(sub, ast.Attribute) \
                    and cls._SHARDY.search(sub.attr):
                return True
            if isinstance(sub, ast.keyword) and sub.arg \
                    and cls._SHARDY.search(sub.arg):
                return True
            if isinstance(sub, ast.arg) and cls._SHARDY.search(sub.arg):
                return True
        return False


# ---------------------------------------------------------------------------
# GL016 — sampling-recompile-key
# ---------------------------------------------------------------------------

@register
class SamplingRecompileKeyRule(Rule):
    """Sampling params as jit static args or executable-cache-key parts."""

    id = "GL016"
    name = "sampling-recompile-key"
    rationale = (
        "Decode serves ONE step executable for every request mix; sampling "
        "params (temperature / top_k / top_p / seed) ride as batch-shaped "
        "array operands of that executable (decode/sampling.py). The "
        "moment one of them becomes a `jax.jit` static argument or a "
        "component of an executable-cache key, every novel value triggers "
        "a fresh trace+compile in the serving hot path — seconds of XLA "
        "per REQUEST, an unbounded executable cache, and a latency cliff "
        "that only shows under parameter-diverse traffic (the single-user "
        "smoke test never sees it). In serving/ and decode/, sampling "
        "params must never be static args or cache-key components.")

    #: the modules whose executables serve per-request traffic
    HOT_PREFIXES = ("deeplearning4j_tpu/serving/",
                    "deeplearning4j_tpu/decode/")
    #: identifier shapes of per-request sampling knobs; matched on whole
    #: underscore-separated words so `seed_bucket` hits but `reseed` and
    #: `processed` don't
    _SAMPLING = re.compile(
        r"(^|_)(temperature|temp|top_k|topk|top_p|topp|seed|sampler|"
        r"sampling)($|_)", re.IGNORECASE)
    _JIT = ("jax.jit", "jax.pjit")
    #: dict methods whose first argument is a lookup key
    _KEYED = ("get", "setdefault", "pop")

    def check(self, ctx):
        if not ctx.rel_path.startswith(self.HOT_PREFIXES):
            return
        aliases = ctx.aliases
        for node in ctx.nodes:
            if isinstance(node, ast.Call):
                if self._is_jit(node, aliases):
                    yield from self._check_jit(ctx, node, aliases)
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr in self._KEYED and node.args:
                    hit = self._sampling_key(node.args[0])
                    if hit:
                        yield self.violation(
                            ctx, node, self._key_msg(hit, node.func.attr))
            elif isinstance(node, ast.Subscript):
                hit = self._sampling_key(node.slice)
                if hit:
                    yield self.violation(
                        ctx, node, self._key_msg(hit, "subscript"))

    # -- jit static args -----------------------------------------------------
    @classmethod
    def _is_jit(cls, node, aliases):
        """jax.jit(...) directly, or functools.partial(jax.jit, ...) as the
        decorator spelling."""
        qual = call_qual(node, aliases)
        if qual in cls._JIT:
            return True
        return (qual == "functools.partial" and node.args
                and qualname(node.args[0], aliases) in cls._JIT)

    def _check_jit(self, ctx, node, aliases):
        nums = []
        for kw in node.keywords:
            if kw.arg == "static_argnames":
                for name in self._str_consts(kw.value):
                    if self._SAMPLING.search(name):
                        yield self.violation(
                            ctx, node,
                            f"static_argnames={name!r}: a sampling param as "
                            "a jit static arg retraces the decode "
                            "executable for every novel value — pass it as "
                            "a batch-shaped array operand "
                            "(sampling.batch_operands) instead")
            elif kw.arg == "static_argnums":
                nums = self._int_consts(kw.value)
        if nums:
            params = self._callee_params(ctx, node, aliases)
            for i in nums:
                if params and -len(params) <= i < len(params) \
                        and self._SAMPLING.search(params[i]):
                    yield self.violation(
                        ctx, node,
                        f"static_argnums includes `{params[i]}`: a sampling "
                        "param as a jit static arg retraces the decode "
                        "executable for every novel value — pass it as a "
                        "batch-shaped array operand "
                        "(sampling.batch_operands) instead")

    @staticmethod
    def _str_consts(node):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return [node.value]
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return [e.value for e in node.elts
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, str)]
        return []

    @staticmethod
    def _int_consts(node):
        if isinstance(node, ast.Constant) and isinstance(node.value, int):
            return [node.value]
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return [e.value for e in node.elts
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, int)]
        return []

    @classmethod
    def _callee_params(cls, ctx, node, aliases):
        """Positional param names of the function being jitted, where a
        shallow look can resolve them: an inline lambda, a module-level def
        named by the first argument, or — for the decorator spelling — the
        decorated function itself."""
        callee = None
        for arg in node.args:
            if qualname(arg, aliases) in cls._JIT:
                continue                    # partial(jax.jit, ...)'s target
            callee = arg
            break
        if isinstance(callee, ast.Lambda):
            return [a.arg for a in callee.args.args]
        if isinstance(callee, ast.Name):
            for n in ctx.nodes:
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and n.name == callee.id:
                    return [a.arg for a in n.args.args]
            return None
        fn = enclosing_function(ctx, node)
        if fn is not None and any(
                node is d or any(node is w for w in ast.walk(d))
                for d in fn.decorator_list):
            return [a.arg for a in fn.args.args]
        return None

    # -- cache keys ----------------------------------------------------------
    @classmethod
    def _sampling_key(cls, expr):
        """A sampling value used AS a lookup key: the bare Name/Attribute
        itself (`fns[cfg.seed]`), or anywhere inside a composite
        Tuple/f-string key (`fns[(L, temperature)]`, `fns[f"s:{seed}"]`).
        Two shapes deliberately stay quiet: string CONSTANTS
        (`operands["temperature"]` is the legitimate operand-dict read —
        the field NAME is fixed, the values live in the array), and
        arithmetic index expressions (`sorted_p[top_k - 1]` is array math
        on a filtered distribution, not an executable-cache key)."""
        if isinstance(expr, (ast.Name, ast.Attribute)):
            return cls._ident_match(expr)
        if isinstance(expr, (ast.Tuple, ast.JoinedStr)):
            for sub in ast.walk(expr):
                hit = cls._ident_match(sub)
                if hit:
                    return hit
        return None

    @classmethod
    def _ident_match(cls, node):
        if isinstance(node, ast.Name) and cls._SAMPLING.search(node.id):
            return node.id
        if isinstance(node, ast.Attribute) \
                and cls._SAMPLING.search(node.attr):
            return node.attr
        return None

    @staticmethod
    def _key_msg(ident, via):
        return (f"sampling param `{ident}` flows into a lookup key "
                f"({via}): keyed executables/caches grow one entry per "
                "novel value and each miss is a fresh trace+compile in "
                "the decode hot path — key by SHAPE (bucket, window, "
                "slot count) and pass sampling values as array operands")


# ---------------------------------------------------------------------------
# GL017 — untracked-jit-cache
# ---------------------------------------------------------------------------

@register
class UntrackedJitCacheRule(Rule):
    """jax.jit result stored into an executable cache without telemetry."""

    id = "GL017"
    name = "untracked-jit-cache"
    rationale = (
        "Every executable the hot modules cache (`self._jit_cache[key]`, "
        "decode step tables, bucket dicts) is supposed to funnel through "
        "the compile-telemetry seam — `timed_first_call` / `CompileTracker` "
        "— which is also where the live cost plane (telemetry/cost.py) "
        "captures XLA's flops/bytes for `/profile/cost`. A bare "
        "`cache[key] = jax.jit(fn)` compiles and dispatches INVISIBLY: no "
        "jit_compiles_total counter, no compile-time gauge, no cost row — "
        "ISSUE 19's whole failure mode of 'which executable is eating the "
        "bandwidth' with one row missing. In serving/, decode/, and nn/, "
        "wrap the jitted callable in timed_first_call(..., label) (or route "
        "it through CompileTracker/the cost registry) before caching it.")

    #: the modules whose cached executables must show up in cost telemetry
    HOT_PREFIXES = ("deeplearning4j_tpu/serving/",
                    "deeplearning4j_tpu/decode/",
                    "deeplearning4j_tpu/nn/")
    _JIT = ("jax.jit", "jax.pjit")
    #: wrapper callables that route the compile through the telemetry plane;
    #: matched on the resolved qualname's last component so both
    #: `timed_first_call(...)` and `xla.timed_first_call(...)` count
    _TRACKED = frozenset({"timed_first_call", "capture", "capture_compiled"})
    #: dict methods that store their second argument under a key
    _STORES = ("setdefault",)

    def check(self, ctx):
        if not ctx.rel_path.startswith(self.HOT_PREFIXES):
            return
        aliases = ctx.aliases
        for node in ctx.nodes:
            if not (isinstance(node, ast.Call)
                    and call_qual(node, aliases) in self._JIT):
                continue
            store = self._cache_store(ctx, node, aliases)
            if store is not None:
                yield self.violation(
                    ctx, store,
                    "jax.jit result stored into an executable cache without "
                    "compile telemetry: wrap it in timed_first_call(jit_fn, "
                    "\"<label>\") so jit_compiles_total / compile seconds / "
                    "the /profile/cost row exist for this executable")

    def _cache_store(self, ctx, jit_call, aliases):
        """The store statement if this jit call's value lands directly in a
        subscript assignment or dict.setdefault WITHOUT passing through a
        tracked wrapper on the way; None otherwise (returns, local names,
        and anything opaque stay quiet — shallow and sound-enough)."""
        child = jit_call
        for anc in ctx.ancestors(jit_call):
            if isinstance(anc, ast.Call):
                fn = anc.func
                last = fn.attr if isinstance(fn, ast.Attribute) else (
                    fn.id if isinstance(fn, ast.Name) else None)
                qual = qualname(fn, aliases)
                if qual is not None:
                    last = qual.rsplit(".", 1)[-1]
                if last in self._TRACKED:
                    return None               # routed through telemetry
                if last in self._STORES and len(anc.args) >= 2 \
                        and child is anc.args[1]:
                    return anc                # d.setdefault(key, jax.jit(...))
            elif isinstance(anc, ast.Assign):
                if child is anc.value and any(
                        isinstance(t, ast.Subscript) for t in anc.targets):
                    return anc                # cache[key] = jax.jit(...)
                return None
            elif isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda, ast.Return, ast.Module)):
                return None
            child = anc
        return None
