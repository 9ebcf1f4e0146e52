"""One run of one cell: set-up, the measured window, the check.

``bench/run.py`` is the entry point; this module holds the run so that
the sweep, the control and the tests drive the same code.  Everything a
cell needs is found by name: its entry in ``BENCHMARK.json``, its
configuration file (``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``), the limits of its check
(``bench/limits/<cell>.json``) and one reader per per-layer metric
(``bench/metrics/<metric>.py``).  From the program it takes the system
under test (``ChunkedServer.serve_online`` over the program's own
configuration of the architecture), its ``Tracer`` records and metric
registry, and the names of its compiled programs.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from bench import peaks as peaks_mod
from bench import reference, trace_reduce, traffic, weights, work

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, ".bench_out")
TRACE_SECONDS = 3.0          # profiler span inside a traced window
TRACE_AT = 0.3               # ... starting at this share of the window
DRAIN_LIMIT_S = 180.0        # after the window, unfinished = failed


class NoChip(SystemExit):
    """The measuring path found no accelerator, or too few."""


# -- the cell, by name --------------------------------------------------

def load_benchmark(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Dict[str, Any]:
    """The cell's entry, configuration file, mix, limits and metrics."""
    bm = load_benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    conf_entry = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    with open(os.path.join(root, conf_entry["file"])) as f:
        conf = json.load(f)
    with open(os.path.join(root, "bench", "limits", name + ".json")) as f:
        limits = json.load(f)
    e2e = [m for m in bm["end_to_end"]
           if name in m.get("workloads", [name])]
    layer = [m for m in bm["per_layer"]
             if name in m.get("workloads", [name])]
    return {"cell": cell, "conf": conf,
            "mix": traffic.load_mix(cell["traffic"],
                                    os.path.join(root, "bench")),
            "limits": limits, "end_to_end": e2e, "per_layer": layer,
            "run_seconds": bm["run_seconds"]}


def metric_reader(name: str, root: str = ROOT) -> Callable:
    """``read(ctx) -> float | None`` of one per-layer metric."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- the device -----------------------------------------------------------

def require_chips(n: int) -> List[Any]:
    """The measuring path: ``n`` accelerators or a nonzero exit."""
    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        raise NoChip(f"bench: needs an accelerator, JAX found "
                     f"{devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"bench: the cell needs {n} chips, JAX found "
                     f"{len(devs)}")
    return devs[:n]


def device_info(devs) -> Dict[str, Any]:
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, or
    where ``JAX_COMPILATION_CACHE_DIR`` says; every program is kept."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(root, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Counts compilations (``jax.monitoring``) and their seconds; one
    listener per process."""

    _one: Optional["CompileLog"] = None

    @classmethod
    def get(cls) -> "CompileLog":
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def __init__(self):
        self.events: List[tuple] = []
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._dur)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), secs))

    def count(self, t0: float = 0.0, t1: float = float("inf")) -> int:
        return sum(t0 <= t <= t1 for t, _ in self.events)

    def seconds(self, t0: float = 0.0, t1: float = float("inf")) -> float:
        return sum(s for t, s in self.events if t0 <= t <= t1)


# -- the program ----------------------------------------------------------

def program_config(conf: Dict):
    """The program's configuration of the architecture, cut as the file
    says; every width must agree with the file."""
    from repro.configs import get_config, reduced_config
    # "reduced" is the program's own CPU-test preset (tests only)
    base = (reduced_config(conf["arch"])
            if conf.get("program_preset") == "reduced"
            else get_config(conf["arch"]))
    cfg = dataclasses.replace(base, num_layers=conf["num_hidden_layers"])
    want = {"d_model": conf["hidden_size"],
            "num_heads": conf["num_attention_heads"],
            "num_kv_heads": conf["num_key_value_heads"],
            "head_dim": conf["head_dim"],
            "d_ff": conf["intermediate_size"],
            "vocab_size": conf["vocab_size"],
            "rope_theta": conf["rope_theta"],
            "use_bias": conf["attention_bias"]}
    for k, v in want.items():
        if getattr(cfg, k) != v:
            raise ValueError(f"program config {conf['arch']}: {k} = "
                             f"{getattr(cfg, k)}, the file says {v}")
    return cfg


def leaf_names(tree) -> List[str]:
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [".".join(str(getattr(k, "key", k)) for k in p) for p, _ in paths]


def make_params(cfg, conf: Dict, seed: int):
    """The serving weights: every leaf of the program's parameter tree,
    drawn on the device by bench/weights.py in one program per shape."""
    from repro.models import api
    shapes = jax.eval_shape(lambda: api.init(cfg, jax.random.PRNGKey(0),
                                             dtype=cfg.dtype))
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    dm = weights.dims(conf)
    out = [weights.make_leaf(seed, n, s.shape, dm, dtype=s.dtype)
           for n, s in zip(leaf_names(shapes), leaves)]
    return jax.block_until_ready(jax.tree_util.tree_unflatten(treedef, out))


def make_tracer():
    """The program's Tracer, timing its own calls (its overhead)."""
    from repro.obs.tracer import Tracer

    class TimedTracer(Tracer):
        own_s = 0.0

    for meth in ("event", "span", "enqueue", "admit", "first_token",
                 "finish"):
        base = getattr(Tracer, meth)

        def timed(self, *a, _base=base, **k):
            t = time.perf_counter()
            _base(self, *a, **k)
            self.own_s += time.perf_counter() - t
        setattr(TimedTracer, meth, timed)
    return TimedTracer()


def to_requests(items: List[traffic.Item]):
    from repro.models.sampling import SamplingParams
    from repro.runtime.arrivals import TimedRequest
    from repro.runtime.server import Request
    return [TimedRequest(it.t_arrival, Request(
        rid=it.rid, prompt=it.prompt, max_new=it.max_new,
        sampling=SamplingParams(temperature=it.temperature,
                                top_p=it.top_p, seed=it.sample_seed)))
        for it in items]


class DrainTimeout(Exception):
    pass


@dataclasses.dataclass
class Dispatch:
    kind: str                   # "chunk" | "span"
    t0: float
    t1: float
    flops: float
    nbytes: float = 0.0


class Driver:
    """Hooks on one server's scheduler: drain deadline, generator
    lateness, and (traced runs) the profiler window, the benchmark's
    host spans and the work of each dispatch."""

    def __init__(self, srv, dm):
        self.srv, self.dm = srv, dm
        self.deadline = float("inf")
        self.lateness: List[float] = []
        self._seen = set()
        self.dispatches: List[Dispatch] = []
        self.trace_at = self.trace_until = None
        self.trace_dir: Optional[str] = None
        self.trace_bounds: Optional[tuple] = None
        self._tracing = False
        admit, chunk, span, harvest = (srv._admit, srv._run_chunk_step,
                                       srv._run_decode_span, srv._harvest)

        def _admit(queue):
            now = time.perf_counter()
            if now > self.deadline:
                raise DrainTimeout()
            for r in queue:
                if r.rid not in self._seen:
                    self._seen.add(r.rid)
                    rec = srv.obs.requests.get(r.rid)
                    if rec is not None and rec.t_enqueue is not None:
                        self.lateness.append(now - rec.t_enqueue)
            self._profiler(now)
            with self._span("bench.admit"):
                return admit(queue)

        def _chunk():
            rows = self._chunk_rows() if self._tracing else None
            t0 = time.perf_counter()
            with self._span("bench.chunk_step"):
                out = chunk()
            if rows is not None:
                self.dispatches.append(Dispatch(
                    "chunk", t0, time.perf_counter(),
                    *work.chunk_work(self.dm, rows)))
            return out

        def _span_call():
            if not self._tracing:
                return span()
            kv = [int(p) for p, m in zip(srv.pos, srv.mode) if m == "decode"]
            before = int(srv.out_len.sum())
            t0 = time.perf_counter()
            with self._span("bench.decode_span"):
                out = span()
            f, b = work.span_work(self.dm, kv, srv.span,
                                  int(srv.out_len.sum()) - before)
            self.dispatches.append(Dispatch("span", t0, time.perf_counter(),
                                            f, b))
            return out

        def _harvest():
            with self._span("bench.harvest"):
                return harvest()

        srv._admit, srv._run_chunk_step = _admit, _chunk
        srv._run_decode_span, srv._harvest = _span_call, _harvest

    def _span(self, name):
        import contextlib
        if not self._tracing:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    def _chunk_rows(self):
        srv, rows = self.srv, []
        for s, req in enumerate(srv.slot_req):
            if req is None:
                continue
            if srv.mode[s] == "prefill":
                off = int(srv.prompt_off[s])
                n = min(srv.chunk, len(req.prompt) - off)
                rows.append((int(srv.pos[s]), n, off + n == len(req.prompt)))
            elif srv.mode[s] == "decode":
                rows.append((int(srv.pos[s]), 1, True))
        return rows

    def plan_trace(self, t_start: float, seconds: float, trace_dir: str):
        self.trace_at = t_start + TRACE_AT * seconds
        self.trace_until = self.trace_at + min(TRACE_SECONDS, seconds / 2)
        self.trace_dir = trace_dir

    def _profiler(self, now: float):
        if self.trace_at is None:
            return
        if not self._tracing and self.trace_bounds is None \
                and now >= self.trace_at:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(self.trace_dir)
            self._tracing = True
            self.trace_bounds = (time.perf_counter(), None)
        elif self._tracing and now >= self.trace_until:
            self.stop()

    def stop(self):
        if self._tracing:
            self._tracing = False
            self.trace_bounds = (self.trace_bounds[0], time.perf_counter())
            jax.profiler.stop_trace()


# -- one run --------------------------------------------------------------

def pct(values, q) -> Optional[float]:
    vals = [v for v in values if v is not None]
    return float(np.percentile(vals, q)) if vals else None


def warm(srv, items):
    """Serve the set-up traffic and touch every small program the
    window can reach (COW copy, harvest gathers of 1..slots rows)."""
    import jax.numpy as jnp
    from repro.runtime.arrivals import closed_stream
    srv.serve_online(closed_stream([tr.request for tr in
                                    to_requests(items)]))
    if srv.paged:
        z = srv._put(np.int32(0))
        srv.cache = srv._cow_fn(srv.cache, z, z)
    for k in range(1, srv.B + 1):
        jax.block_until_ready(jnp.take(
            srv.out_buf, srv._put(np.zeros(k, np.int32)), axis=0))


def run_cell(c: Dict[str, Any], *, seed: int, seconds: float, trace: bool,
             devs, t_process: float, log: Callable[[str], None],
             control: bool = False) -> Dict[str, Any]:
    """Set up, measure for ``seconds``, check.  Returns the result."""
    from repro.runtime.arrivals import closed_stream
    from repro.runtime.server import ChunkedServer
    conf, mix, cell = c["conf"], c["mix"], c["cell"]
    dm = weights.dims(conf)
    clog = CompileLog.get()
    cfg = program_config(conf)
    t0 = time.perf_counter()
    params = make_params(cfg, conf, seed)
    t_w = time.perf_counter()
    tracer = make_tracer()
    srv = ChunkedServer(cfg, params, batch_slots=conf["batch_slots"],
                        max_len=conf["max_len"], tracer=tracer)
    drv = Driver(srv, dm)
    warm(srv, traffic.warmup_items(mix, seed, dm["V"], srv.B, srv.chunk))
    t_s = time.perf_counter()
    log(f"setup: weights {t_w - t0:.2f}s, compile {clog.seconds(t0, t_s):.2f}s"
        f" ({clog.count(t0, t_s)} programs), warm-up "
        f"{t_s - t_w - clog.seconds(t_w, t_s):.2f}s, process start to "
        f"window {t_s - t_process:.2f}s")
    tracer.clear()
    tracer.own_s = 0.0
    if trace:
        drv.plan_trace(t_s, seconds, os.path.join(OUT, "trace",
                                                  cell["name"]))
    offline = mix["kind"] == "waves"
    items: List[traffic.Item] = []
    sent: Dict[int, Any] = {}
    totals: Dict[str, float] = {}
    drv.deadline = t_s + seconds + DRAIN_LIMIT_S
    t_end = None
    try:
        if offline:
            k = 0
            while time.perf_counter() - t_s < seconds:
                wv = traffic.wave(mix, seed, dm["V"], k)
                items.extend(wv)
                reqs = [tr.request for tr in to_requests(wv)]
                sent.update((r.rid, r) for r in reqs)
                srv.serve_online(closed_stream(reqs))
                t_end = time.perf_counter()
                add_totals(totals, srv.metrics)
                k += 1
        else:
            items = traffic.open_loop(mix, seed, dm["V"], seconds)
            stream = to_requests(items)
            sent.update((tr.request.rid, tr.request) for tr in stream)
            srv.serve_online(stream)
            t_end = time.perf_counter()
            add_totals(totals, srv.metrics)
    except DrainTimeout:
        log(f"drain: stopped {DRAIN_LIMIT_S:.0f}s after the window")
    drv.stop()
    t_e = time.perf_counter()
    window_compiles = clog.count(t_s, t_e)
    recs = {r.rid: r for r in tracer.request_records() if r.rid >= 0}
    info = device_info(devs) if devs else {}
    served = collect_outputs(sent)
    failed = [it.rid for it in items
              if not served.get(it.rid, {}).get("ok", False)]
    lat = sorted(drv.lateness)
    log(f"window: {len(items)} requests, {len(failed)} failed, "
        f"{t_e - t_s:.2f}s to the last finish; compiles in window "
        f"{window_compiles}; arrival lateness p50 "
        f"{_ms(pct(lat, 50))} max {_ms(lat[-1] if lat else None)}; "
        f"tracer own time {tracer.own_s * 1e3:.1f} ms "
        f"({100 * tracer.own_s / max(t_e - t_s, 1e-9):.3f}% of the window)")
    tt = [_ttft(recs.get(it.rid)) for it in items]
    tp = [recs[it.rid].tpot_s for it in items
          if it.rid in recs and recs[it.rid].tpot_s is not None]
    e2e = {"setup_s": (t_s - t_process, "s"),
           "ttft_p90_ms": (_scale(pct(tt, 90), 1e3), "ms"),
           "tpot_p90_ms": (_scale(pct(tp, 90), 1e3), "ms")}
    if offline and t_end is not None:
        toks = sum(len(served[it.rid]["output"]) for it in items
                   if it.rid in served)
        e2e["output_tok_s"] = (toks / (t_end - t_s), "tokens/s")
    log(f"requests: ttft n={sum(x is not None for x in tt)}, tpot "
        f"n={len(tp)}; " + ", ".join(f"{k} {v[0]}" for k, v in e2e.items()))
    ctx = Context(conf=conf, dm=dm, mix=mix, records=recs, items=items,
                  totals=totals, drv=drv, offline=offline,
                  span=srv.span, device_kind=info.get("kind"), note=log)
    # free the program before the reference runs
    del srv, params, drv.srv
    gc.collect()
    check = check_outputs(c, seed, items, served, log, control=control)
    result = {"correct": bool(check["ok"] and not failed),
              "attempted": len(items), "failed": len(failed),
              "device": info, "check": check["numbers"]}
    if trace:
        layer, extra = per_layer(c, ctx, log)
        result["metrics"] = layer
        result["device"].update(extra.get("device", {}))
        if "breakdown" in extra:
            result["breakdown"] = extra["breakdown"]
    else:
        # a tail over a request that never finished is infinite: such a
        # run has failed, and the metric is left out of its line
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]][0],
                                         "unit": m["unit"]}
                             for m in c["end_to_end"]
                             if np.isfinite(e2e.get(m["name"],
                                                    (np.nan,))[0] or np.nan)}
    result["window_compiles"] = window_compiles
    if control:
        result["control"] = check.get("control")
    return result


TOTALS = ("serving.dispatches.prefill", "serving.dispatches.span")
HIST_TOTALS = ("serving.wall_s.prefill", "serving.wall_s.span")


def add_totals(totals: Dict[str, float], registry) -> None:
    """Add one ``serve_online`` call's dispatch counts and wall seconds
    (each call resets the program's registry)."""
    for k in TOTALS:
        totals[k] = totals.get(k, 0) + registry.counter_value(k)
    for k in HIST_TOTALS:
        totals[k] = totals.get(k, 0.0) + registry.hist_total(k)


def _ttft(rec) -> Optional[float]:
    """A request with no first token counts as infinitely late."""
    if rec is None or rec.ttft_s is None:
        return float("inf")
    return rec.ttft_s


def _scale(v, k):
    return None if v is None else v * k


def _ms(v):
    return "n/a" if v is None else f"{v * 1e3:.2f}ms"


def collect_outputs(sent: Dict[int, Any]) -> Dict[int, Dict]:
    """Each sent request's output, and whether it finished whole."""
    return {rid: {"ok": bool(r.done and not r.truncated
                             and len(r.output) == r.max_new),
                  "output": list(r.output)}
            for rid, r in sent.items()}


# -- the check ------------------------------------------------------------

def choose_sample(items, served, seed: int, pack_tokens: int
                  ) -> List[traffic.Item]:
    """Finished greedy requests for the reference: the longest, then
    others in an order drawn from the seed, while they fit the pack."""
    cand = [it for it in items if it.greedy and served.get(it.rid, {}).get("ok")]
    if not cand:
        return []
    size = lambda it: len(it.prompt) + len(served[it.rid]["output"]) - 1  # noqa: E731
    longest = max(cand, key=size)
    rng = np.random.default_rng([int(seed) % 2**63, 11])
    rest = [cand[i] for i in rng.permutation(len(cand)) if cand[i] is not longest]
    chosen, used = [], 0
    for it in [longest] + rest:
        if used + size(it) <= pack_tokens:
            chosen.append(it)
            used += size(it)
    return chosen


def check_outputs(c, seed, items, served, log, *, control=False):
    """Widest gap between the reference's best logit and its logit for
    a served greedy token, against the cell's limit."""
    conf, mix, lim = c["conf"], c["mix"], c["limits"]
    pack_tokens = traffic.check_pack_tokens(mix)
    sample = choose_sample(items, served, seed, pack_tokens)
    if not sample:
        log("check: no finished greedy request to compare")
        return {"ok": False, "numbers": {}}
    t0 = time.perf_counter()
    res = reference.run(conf, seed, [it.prompt for it in sample],
                        [served[it.rid]["output"] for it in sample],
                        pack_tokens, control=control)
    gap = reference.gaps(res)
    widest = float(gap.max())
    numbers = {"max_logit_gap": {"value": widest,
                                 "limit": lim["max_logit_gap"]}}
    log(f"check: {len(sample)} greedy requests, {len(gap)} served tokens, "
        f"reference {time.perf_counter() - t0:.1f}s; widest logit gap "
        f"{widest:.6f} (limit {lim['max_logit_gap']}), mean "
        f"{float(gap.mean()):.6f}, tokens off the reference's top "
        f"{int((gap > 0).sum())}")
    out = {"ok": widest <= lim["max_logit_gap"], "numbers": numbers}
    if control:
        cg = reference.gaps(res, "control")
        out["control"] = {"max_logit_gap": float(cg.max()),
                          "mean": float(cg.mean()),
                          "off_top": int((cg > 0).sum()), "n": len(cg)}
        log(f"control (fp8 weights): widest gap {float(cg.max()):.6f}, "
            f"tokens off the reference's top {int((cg > 0).sum())}")
    return out


# -- per-layer metrics -----------------------------------------------------

@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""
    conf: Dict
    dm: Dict[str, int]
    mix: Dict
    records: Dict[int, Any]
    items: List[traffic.Item]
    totals: Dict[str, float]
    drv: Any
    offline: bool
    span: int
    device_kind: Optional[str]
    trace: Optional[trace_reduce.Trace] = None
    note: Callable[[str], None] = print

    @property
    def peaks(self):
        return peaks_mod.peaks(self.device_kind)

    def traced(self, kind: str) -> List[Dispatch]:
        """Dispatches of ``kind`` wholly inside the profiler window."""
        b = self.drv.trace_bounds
        if not b or b[1] is None:
            return []
        return [d for d in self.drv.dispatches
                if d.kind == kind and b[0] <= d.t0 and d.t1 <= b[1]]


def per_layer(c, ctx: Context, log):
    extra: Dict[str, Any] = {}
    path = (trace_reduce.find_trace(ctx.drv.trace_dir)
            if ctx.drv.trace_dir else None)
    if path:
        trace = trace_reduce.load(path)
        ctx.trace = trace if trace.ops else None
    if ctx.trace is not None:
        bw = trace_reduce.busy_and_window(ctx.trace)
        if bw:
            extra["device"] = {"busy_s": bw[0], "window_s": bw[1]}
            log(f"trace: {os.path.relpath(path, ROOT)}; busy {bw[0]:.6f}s "
                f"of {bw[1]:.6f}s")
        extra["breakdown"] = {"device_ops": trace_reduce.top_ops(ctx.trace),
                              "idle_gaps": trace_reduce.idle_gaps(ctx.trace)}
    out = {}
    for m in c["per_layer"]:
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out, extra


def summary_line(result: Dict) -> str:
    nums = result.get("check", {})
    parts = [f"{k} {v['value']:.6f} limit {v['limit']}"
             for k, v in nums.items()]
    return "compared: " + ("; ".join(parts) if parts else "nothing")
