"""One run of one cell: build the served stack, set it up, drive the
window open-loop, check the outputs, reduce the spans and the trace.

The stack is the program's own, built from its public pieces as
``repro.launch.serve.serve`` builds it: ``build_model`` and
``init_params`` (weights made on the device from the seed), a
``ModelServer``, a ``ResourceManager`` and ``BatchSystem`` with one lease
held by an ``Invoker``, and a ``ServeEngine`` with the configuration's
batch.  The window drives ``ServeEngine.enqueue``/``run``: between waves
one thread enqueues up to ``batch`` requests that are due, then calls
``run()``, which serves them as one wave.  Every request is timed from
when it was due.
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import HERE, ROOT, check, spans as S, stats, traffic as T

# the source's key names -> the program's ArchConfig fields
ARCH_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "intermediate_size": "d_ff", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "vocab_size": "vocab_size", "sliding_window": "sliding_window",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "hidden_act": "act",
    "torch_dtype": "dtype",
}
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def process_start() -> float:
    """When this process started, on ``time.monotonic``'s clock."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.monotonic() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.monotonic()


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """Everything one run needs, resolved from ``BENCHMARK.json``."""
    workload: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    metrics: List[dict] = field(default_factory=list)   # per-layer entries
    end_to_end: List[dict] = field(default_factory=list)


def resolve(workload: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(os.path.join(root, conf["file"]))
    here = os.path.join(root, os.path.basename(HERE))
    traffic = T.load(os.path.join(here, "traffic", w["traffic"] + ".json"))
    limits = load_json(os.path.join(here, "limits", workload + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(workload, w["chips"], config, traffic, limits, per_layer,
                e2e)


def arch_config(config: dict):
    from repro.configs import get_config
    kw = {ARCH_KEYS[k]: v for k, v in config.items() if k in ARCH_KEYS}
    if kw.get("sliding_window") is None:
        kw["sliding_window"] = 0
    return get_config(config["arch"]).replace(**kw)


def devices_for(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} devices, found {len(devs)}")
    return devs[:chips]


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in chipbench/"
                       "peaks.json; add its published peaks")
    return table["devices"][kind]


class CompileCounter:
    """Counts programs compiled, or loaded from the persistent cache."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw):
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


class GcPauses:
    """Python's garbage collections while open: how many of each
    generation, and the longest pause, which stalls every thread."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.longest_s = 0.0
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.count[info["generation"]] += 1
            self.longest_s = max(self.longest_s, time.perf_counter() - self._t)

    def close(self):
        gc.callbacks.remove(self._on)


@dataclass
class Context:
    """What a per-layer metric reader gets."""
    workload: str
    config: dict
    chips: int
    peaks: dict
    spans: S.Spans
    requests: List[stats.Served]
    window_start: float
    run_end: float
    trace: Optional[object] = None          # trace_reduce.TraceSummary


@dataclass
class Outcome:
    result: dict
    notes: List[str]


def _wave_token_times(invokes: List[S.Span], n_tokens: int) -> List[float]:
    """Arrival of a row's tokens: the prefill's return, then each decode's
    return, as the client saw them."""
    times = [s.end for s in invokes if s.name in ("invoke.prefill",
                                                  "invoke.decode")]
    return times[:n_tokens]


class Stack:
    """The served stack of one configuration, with weights from ``seed``,
    set up for the prompt lengths it will be sent."""

    def __init__(self, config: dict, chips: int, seed: int,
                 prompt_lens, *, annotate: bool, require_chip: bool = True):
        import jax
        from repro.core import BatchSystem, Invoker, Ledger, ResourceManager
        from repro.distribution.context import make_context
        from repro.launch.mesh import make_mesh
        from repro.launch.serve import init_params
        from repro.models.factory import build_model
        from repro.serving import ModelServer, ServeEngine

        self.devices = devices_for(chips, require_chip)
        self.kind = self.devices[0].device_kind
        self.peaks = peaks_for(self.kind) if require_chip else {}
        self.counter = CompileCounter()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        mesh = None
        if config.get("mesh"):
            m = config["mesh"]
            mesh = make_mesh(tuple(m["shape"]), tuple(m["axes"]),
                             devices=self.devices)
        self.cfg = arch_config(config)
        model = build_model(self.cfg, make_context(mesh))
        t = time.monotonic()
        params = jax.block_until_ready(init_params(model, seed))
        self.timings = {"weights_s": time.monotonic() - t}
        server = ModelServer(model, params, max_len=config["max_len"])
        del params, model
        self.spans = S.Spans(annotate=annotate)
        # the program's own library, its functions timed where they run
        lib = server.make_library()
        self._drop_server = S.time_library(lib, self.spans)
        del server
        self.rm = ResourceManager(n_replicas=2)
        cluster = BatchSystem(self.rm, Ledger(), n_nodes=2,
                              workers_per_node=2, hot_period=10.0)
        cluster.release_idle()
        self.rm.start_heartbeats()
        self.invoker = Invoker("bench", self.rm, lib, seed=seed)
        self.batch = config["batch"]
        self.invoker.allocate(1)
        proxy = S.TimedInvoker(self.invoker, self.spans)
        # every wave size at every prompt length: one prefill and one
        # decode program each, compiled or loaded here, not in the window
        t = time.monotonic()
        warm = ServeEngine(proxy, batch_size=self.batch)
        rng = np.random.default_rng([int(seed), 2])
        for length in sorted(set(prompt_lens)):
            for b in range(1, self.batch + 1):
                for _ in range(b):
                    warm.enqueue(rng.integers(1, self.cfg.vocab_size, length),
                                 max_new_tokens=2)
                warm.run()
        self.timings["warm_waves_s"] = time.monotonic() - t
        self.engine = ServeEngine(proxy, batch_size=self.batch)

    def serve(self, sched: List[T.Request], prompts: List[np.ndarray],
              stop_after: Optional[float] = None,
              log: Callable[[str], None] = print):
        """Drive one window open-loop from now.  Returns the requests'
        records, the window's start and the run's end.  With
        ``stop_after`` no wave starts later than that many seconds into
        the window, and requests not started by then stay unserved."""
        sp, engine, batch = self.spans, self.engine, self.batch
        t0 = time.monotonic()
        reqs = [stats.Served(due=t0 + r.due_s, prompt_len=r.prompt_len)
                for r in sched]
        self.late: List[float] = []
        self.waves = 0
        self.wave_log: List[tuple] = []     # (start after t0, rows)
        i, n = 0, len(sched)
        with sp.span("window"):
            while i < n:
                if stop_after is not None and \
                        time.monotonic() > t0 + stop_after:
                    break
                due = reqs[i].due
                now = time.monotonic()
                if due > now:
                    with sp.span("wait.arrival"):
                        time.sleep(due - now)
                    self.late.append(time.monotonic() - due)
                    continue
                wave = []
                while i < n and len(wave) < batch and \
                        reqs[i].due <= time.monotonic():
                    reqs[i].t_enqueued = time.monotonic()
                    wave.append((i, engine.enqueue(
                        prompts[i], max_new_tokens=sched[i].max_new_tokens)))
                    i += 1
                self.wave_log.append((time.monotonic() - t0, len(wave)))
                mark = len(sp.items)
                try:
                    engine.run()
                    ok = True
                except Exception as e:   # the wave's requests count failed
                    log(f"wave {self.waves} failed: {type(e).__name__}: {e}")
                    ok = False
                invokes = [s for s in sp.items[mark:]
                           if s.name.startswith("invoke.")]
                for j, g in wave:
                    r = reqs[j]
                    if ok:
                        r.tokens = list(g.tokens_out)
                        r.token_times = _wave_token_times(invokes,
                                                          len(g.tokens_out))
                    else:
                        r.failed = True
                self.waves += 1
        return reqs, t0, time.monotonic()

    def peak_bytes(self) -> Optional[int]:
        """Largest ``peak_bytes_in_use`` over the cell's chips."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None

    def close(self):
        """Release the lease and drop the weights and caches, so that
        what runs next has the chip's memory."""
        import jax
        try:
            self.invoker.deallocate()
        finally:
            self.rm.stop()
            self.counter.close()
        self._drop_server()
        self.engine = self.invoker = None
        gc.collect()
        jax.clear_caches()
        return sum(a.nbytes for a in jax.live_arrays())


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, t_process: Optional[float] = None,
             controls=(),
             log: Callable[[str], None] = lambda s: print(s, file=sys.stderr)
             ) -> Outcome:
    import jax

    t_process = process_start() if t_process is None else t_process
    sched = T.schedule(cell.traffic, seconds)
    stack = Stack(cell.config, cell.chips, seed,
                  [r.prompt_len for r in sched], annotate=trace,
                  require_chip=require_chip)
    config = cell.config
    prompts = T.prompts(sched, stack.cfg.vocab_size, seed)
    try:
        trace_dir = None
        if trace:
            trace_dir = os.path.join(HERE, "out", "trace", cell.workload)
            shutil.rmtree(trace_dir, ignore_errors=True)
            # the device's planes and the harness's own annotations only:
            # no Python function events, no runtime internals, no HLO
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            # the profiler's threads start after start_trace returns; a
            # window begun at once found its first wake-up 0.26 s late
            time.sleep(2.0)
        compiles_before = stack.counter.count
        setup_s = time.monotonic() - t_process
        pauses = GcPauses()
        try:
            reqs, t0, run_end = stack.serve(sched, prompts, log=log)
        finally:
            pauses.close()
        compiles = stack.counter.count - compiles_before
        if trace:
            jax.profiler.stop_trace()
        peak = stack.peak_bytes()
    finally:
        live = stack.close()
    n = len(reqs)
    late = stack.late
    starts = [round(t, 3) for t, _ in stack.wave_log]
    notes = [f"setup_s={setup_s:.3f} window_s={seconds} requests={n} "
             f"waves={stack.waves} run_s={run_end - t0:.3f}",
             "wave rows: " + " ".join(str(b) for _, b in stack.wave_log),
             "wave starts_s: " + " ".join(map(str, starts)),
             "generator lateness after each wait: "
             + (f"max_ms={max(late) * 1e3:.3f} "
                f"median_ms={float(np.median(late)) * 1e3:.3f} "
                f"waits={len(late)}" if late else "no waits"),
             "set-up: " + " ".join(f"{k}={v:.3f}"
                                   for k, v in stack.timings.items()),
             f"compiles_in_window={compiles}",
             "gc_in_window: " + " ".join(f"gen{g}={c}" for g, c in
                                         enumerate(pauses.count))
             + f" longest_ms={pauses.longest_s * 1e3:.1f}",
             f"live_bytes_after_free={live}"]
    if compiles:
        raise RuntimeError(f"{compiles} programs compiled inside the "
                           "window; the set-up missed a shape")

    # ------------------------------------------------------------ check
    lim = cell.limits
    ok_idx = [j for j, r in enumerate(reqs) if not r.failed]
    picks = check.sample([len(reqs[j].tokens) for j in ok_idx], seed,
                         lim["sample_requests"])
    picks = [ok_idx[p] for p in picks]
    t_check = time.monotonic()
    out_max = check.max_length(cell.traffic["output_tokens"])
    found = check.gaps(config, seed, [prompts[j] for j in picks],
                       [reqs[j].tokens for j in picks], controls,
                       shape=(lim["sample_requests"], out_max - 1 +
                              check.max_length(cell.traffic["prompt_tokens"]),
                              out_max))
    gap = check.widest(found[0])
    limit = lim["widest_logit_gap"]["limit"]
    checks = {"widest_logit_gap": {"value": gap, "limit": limit},
              "sampled_requests": {"value": len(picks),
                                   "tokens": int(sum(len(g)
                                                     for g in found[0]))}}
    for name, per in zip(controls, found[1:]):
        checks[f"control_{name}_widest_logit_gap"] = {
            "value": check.widest(per), "limit": limit}
    notes.append(f"check_s={time.monotonic() - t_check:.3f}")
    correct = bool(gap <= limit)

    # ---------------------------------------------------------- metrics
    d0 = stack.devices[0]
    device = {"platform": d0.platform, "kind": stack.kind,
              "count": len(stack.devices), "memory_peak_bytes": peak}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.metrics}
    metrics: Dict[str, dict] = {}
    breakdown = None
    if not trace:
        e2e = stats.end_to_end(reqs, t0, run_end)
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        from chipbench import trace_reduce
        t_trace = time.monotonic()
        path = trace_reduce.find(trace_dir)
        summary = trace_reduce.reduce(path)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = {"device_ops": summary.top_ops(),
                     "idle_gaps": summary.idle_gaps()}
        ctx = Context(cell.workload, config, cell.chips, stack.peaks,
                      stack.spans, reqs, t0, run_end, summary)
        for m in cell.metrics:
            value = read_metric(m["name"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value,
                                      "unit": units[m["name"]]}
        notes.append(f"trace {path} {os.path.getsize(path)} bytes, "
                     f"reduced in {time.monotonic() - t_trace:.3f} s")
    result = {"correct": correct, "attempted": n,
              "failed": sum(r.failed for r in reqs),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return Outcome(result, notes)


def read_metric(name: str, ctx: Context):
    """Run ``chipbench/metrics/<name>.py``'s ``read(ctx)``."""
    import importlib.util
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench.metrics." + name.replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)
