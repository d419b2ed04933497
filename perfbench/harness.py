"""The benchmark harness: one cell, one run, one result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name `BENCHMARK.json`
gives it:

* ``perfbench/configs/<config>.json``: the deployment (population, graph,
  channel, mesh, policy, NetES settings, precision) and the limits of
  the comparison that decides `correct`;
* ``perfbench/traffic/<traffic>.json``: the task and the evaluation
  protocol that drive the training loop;
* ``perfbench/metrics/<metric>.py``: a reader with ``read(ctx)``
  returning a number or None (nothing to read), and optionally
  ``probes(ctx)`` naming program calls to be timed alone on the device.

A run: set-up is one call of the program's entry point
(``repro.train.loop.train_rl_netes``) built from the cell's files and
``--seed``; it builds the graph and the state and runs the first scan
chunk and the first evaluation, which compile or load every program the
window uses. Its first evaluation point (a device sync) opens the window;
the window then covers whole chunks of training and evaluation, each
closed by the program's own device sync at an evaluation point, until
``--seconds`` have passed. The iterations of the first chunk are then
compared with the plain reference (`compare.py`).
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = ROOT / ".jax_cache"
NO_CHIP_EXIT = 3
TRACE_CHUNKS = 2            # chunks inside the profiler's traced window


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


class _WindowClosed(Exception):
    """Raised from the program's log hook to end the timed call."""


def load_json(path: pathlib.Path) -> Dict[str, Any]:
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    """Resolve a workload name through BENCHMARK.json to its files."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    return Cell(name=workload, chips=int(w["chips"]),
                config=load_json(root / conf["file"]),
                traffic=load_json(root / "perfbench" / "traffic"
                                  / f"{w['traffic']}.json"),
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


def load_metric(name: str, root: pathlib.Path = ROOT):
    """The reader module ``perfbench/metrics/<name>.py``."""
    path = root / "perfbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------
# the device
# --------------------------------------------------------------------------

def configure_jax():
    """Persistent compilation cache at a fixed path inside the checkout,
    caching every program however fast it compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_devices(chips: int) -> Dict[str, Any]:
    """The cell's chips, or NoChip. Off a TPU, or on a device kind with
    no published peaks, nothing is measured."""
    import jax

    from perfbench import flops
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, {len(devs)} found")
    kind = devs[0].device_kind
    try:
        flops.peaks(kind)
    except KeyError as e:
        raise NoChip(str(e)) from e
    return {"platform": devs[0].platform, "kind": kind, "count": chips}


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# --------------------------------------------------------------------------
# the program under test
# --------------------------------------------------------------------------

def train_config(cell: Cell, seed: int, iters: int):
    """The cell as the program's own TrainConfig."""
    from repro.core.netes import NetESConfig
    from repro.core.topology import TopologySpec
    from repro.train.loop import TrainConfig
    c, t = cell.config, cell.traffic
    topo = c["topology"]
    return TrainConfig(
        n_agents=c["n_agents"], iters=iters,
        topology=TopologySpec(family=topo["family"], n_agents=c["n_agents"],
                              p=topo["p"], seed=topo["seed"]),
        representation=topo["representation"], channel=c["channel"],
        shards=c["shards"], seed=seed, eval_every=t["eval_every"],
        eval_episodes=t["eval_episodes"], netes=NetESConfig(**c["netes"]))


class FirstChunk:
    """Keeps the per-iteration metrics the program's first scan chunk
    returns (``repro.core.netes.run``, which the training loop calls for
    every chunk), and that call's own input. The wrapper passes every
    call and result through unchanged."""

    KEEP = ("reward_mean", "update_var", "broadcast")

    def __init__(self):
        self.metrics = None
        self.call = None

    def __enter__(self):
        from repro.core import netes
        self._netes, self._orig = netes, netes.run

        def run(*args, **kwargs):
            out = self._orig(*args, **kwargs)
            if self.metrics is None:
                self.metrics = {k: out[-1][k] for k in self.KEEP}
                self.call = (args, kwargs)
            return out

        netes.run = run
        return self

    def __exit__(self, *exc):
        self._netes.run = self._orig

    def follow(self) -> Dict[str, Any]:
        """The program's parameters after iteration 0, from its own entry
        called for one iteration on the first chunk's input: the
        population's mean (float64) before and after, and the first
        agent's row."""
        import jax.numpy as jnp
        import numpy as np
        args, kwargs = self.call
        self.call = None
        before = args[0].thetas
        after = self._orig(*args, **dict(kwargs, num_iters=1))[0].thetas

        def mean(t):
            return np.asarray(t.astype(jnp.float32).mean(axis=0), np.float64)

        return {"theta_mean": [mean(before), mean(after)],
                "row": np.asarray(after[0], np.float64)}


class Counters:
    """XLA compile requests and persistent-cache hits, through the public
    ``jax.monitoring`` listeners. A compile request served from the
    persistent cache is a cache hit; the rest compiled."""

    REQUEST = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.requests = 0
        self.hits = 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self.REQUEST:
            self.requests += 1

    def _event(self, event, **_):
        if event == self.HIT:
            self.hits += 1

    def snapshot(self):
        return {"compiles": self.requests - self.hits, "cache_hits": self.hits}

    def close(self):
        mon = self._jax.monitoring
        mon.unregister_event_duration_listener(self._duration)
        mon.unregister_event_listener(self._event)


@dataclasses.dataclass
class Window:
    stamps: List[float]
    iters_per_chunk: int
    n_agents: int
    counters: Dict[str, int]
    trace_dir: Optional[str] = None
    traced_stamps: Optional[tuple] = None
    evals: List[float] = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.stamps[-1] - self.stamps[0]

    @property
    def iters(self) -> int:
        return (len(self.stamps) - 1) * self.iters_per_chunk

    def rate(self, first: int = 0) -> float:
        """Agent-iterations per second from stamp ``first`` to the end."""
        chunks = len(self.stamps) - 1 - first
        return (self.n_agents * chunks * self.iters_per_chunk
                / (self.stamps[-1] - self.stamps[first]))


def run_window(cell: Cell, seed: int, seconds: float, trace: bool,
               counters: Counters, t_start: float) -> tuple:
    """The timed call. Returns (Window, FirstChunk, setup_s)."""
    import jax

    from repro.train.loop import train_rl_netes
    every = cell.traffic["eval_every"]
    # an upper bound on the call's length; the window closes it early
    tc = train_config(cell, seed, iters=every * 100_000)
    stamps: List[float] = []
    evals: List[float] = []
    state: Dict[str, Any] = {"counters": None, "trace_dir": None,
                             "annotation": None, "traced": None}

    def log(entry):
        now = time.perf_counter()
        stamps.append(now)
        evals.append(entry["eval"])
        k = len(stamps)
        if k == 1:
            state["counters"] = counters.snapshot()
            if trace:
                state["trace_dir"] = tempfile.mkdtemp(prefix="perfbench_")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(state["trace_dir"],
                                         profiler_options=opts)
        elif trace and k == 2:
            state["annotation"] = jax.profiler.TraceAnnotation(
                "perfbench_window")
            state["annotation"].__enter__()
        elif trace and k == 2 + TRACE_CHUNKS:
            state["annotation"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            state["traced"] = (1, k - 1)
        # a traced run keeps two chunks past the trace's end, whose rate
        # is free of the profiler's start and stop
        enough = 4 + TRACE_CHUNKS if trace else 3
        if k >= enough and now - stamps[0] >= seconds:
            raise _WindowClosed

    with FirstChunk() as first:
        try:
            train_rl_netes(cell.traffic["task"], tc, log=log)
        except _WindowClosed:
            pass
        else:
            raise RuntimeError("the training call ended before the window")
    setup_s = stamps[0] - t_start
    end = counters.snapshot()
    window_counts = {k: end[k] - state["counters"][k] for k in end}
    win = Window(stamps=stamps, iters_per_chunk=every,
                 n_agents=cell.config["n_agents"], counters=window_counts,
                 trace_dir=state["trace_dir"],
                 traced_stamps=state["traced"], evals=evals)
    first.metrics = jax.device_get(first.metrics)
    return win, first, setup_s


# --------------------------------------------------------------------------
# per-layer metrics
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """What a metric reader may read."""
    cell: Cell
    window: Window
    peak: Dict[str, float]
    nnz: int
    trace: Any = None                     # trace_reduce.Reduced
    probe_seconds: Dict[str, float] = dataclasses.field(default_factory=dict)


def time_probes(probes: Dict[str, Callable[[], Any]], repeats: int = 3
                ) -> Dict[str, float]:
    """Device seconds per call of each probe: each runs once to compile,
    then ``repeats`` times inside a profiler trace, each call in a host
    annotation of its own; its device time is the union of the device
    operations inside the annotation."""
    import jax

    from perfbench import trace_reduce
    if not probes:
        return {}
    for fn in probes.values():
        jax.block_until_ready(fn())
    tdir = tempfile.mkdtemp(prefix="perfbench_probe_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        for label, fn in probes.items():
            for _ in range(repeats):
                with jax.profiler.TraceAnnotation(f"perfbench_probe:{label}"):
                    jax.block_until_ready(fn())
    finally:
        jax.profiler.stop_trace()
    red = trace_reduce.load(trace_reduce.find_xplane(tdir))
    shutil.rmtree(tdir, ignore_errors=True)
    out = {}
    for label in probes:
        spans = red.host_spans(f"perfbench_probe:{label}")
        if len(spans) != repeats:
            continue
        busy = [red.device_busy(s) for s in spans]
        out[label] = sum(busy) / len(busy)
    return out


def read_per_layer(ctx: Context) -> Dict[str, Dict[str, Any]]:
    mods = {m["name"]: load_metric(m["name"]) for m in ctx.cell.per_layer}
    probes: Dict[str, Callable] = {}
    for mod in mods.values():
        if hasattr(mod, "probes"):
            probes.update(mod.probes(ctx))
    ctx.probe_seconds = time_probes(probes)
    out = {}
    for m in ctx.cell.per_layer:
        value = mods[m["name"]].read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# --------------------------------------------------------------------------
# correctness
# --------------------------------------------------------------------------

def reference_setup(cell: Cell):
    from perfbench import reference
    c = cell.config
    bits = None
    if c["channel"]:
        bits = int(c["channel"].split("bits=")[1].rstrip(")"))
    return reference.Setup(
        n=c["n_agents"], family=c["topology"]["family"],
        p=c["topology"]["p"], topo_seed=c["topology"]["seed"],
        sizes=tuple(c["policy"]["sizes"]), task=cell.traffic["task"],
        alpha=c["netes"]["alpha"], sigma=c["netes"]["sigma"],
        p_broadcast=c["netes"]["p_broadcast"],
        weight_decay=c["netes"]["weight_decay"], quantize_bits=bits)


def check(cell: Cell, seed: int, first: FirstChunk, reference):
    """Compare the first chunk, and the parameters its first iteration
    leads to, with the reference; returns (each number beside its
    limit, whether all are within)."""
    from perfbench import compare
    program = dict(first.metrics, **first.follow())
    gc.collect()
    flags = reference.broadcast_flags(seed, cell.traffic["eval_every"])
    judged = compare.judge(compare.numbers(program, reference.first(seed),
                                           flags),
                           cell.config["correct"])
    return judged, all(v["ok"] for v in judged.values())


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, skip_chip_check: bool = False) -> Dict[str, Any]:
    """One run of ``cell``; returns the result line's object.
    ``skip_chip_check`` lets the tests drive a run on the CPU."""
    from perfbench import flops
    from perfbench import reference as ref_mod
    configure_jax()
    import jax
    if skip_chip_check:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": cell.chips}
    else:
        device = check_devices(cell.chips)
    counters = Counters()
    try:
        win, first, setup_s = run_window(cell, seed, seconds, trace,
                                           counters, t_start)
    finally:
        counters.close()
    device["memory_peak_bytes"] = memory_peak_bytes(cell.chips)
    failed = sum(cell.traffic["eval_every"] for e in win.evals[1:]
                 if not math.isfinite(e))
    reference = ref_mod.Reference(reference_setup(cell))
    breakdown = None
    if trace:
        from perfbench import trace_reduce
        reduced = trace_reduce.load(trace_reduce.find_xplane(win.trace_dir))
        shutil.rmtree(win.trace_dir, ignore_errors=True)
        ctx = Context(cell=cell, window=win,
                      peak=flops.peaks(device["kind"]), nnz=reference.nnz,
                      trace=reduced)
        metrics = read_per_layer(ctx)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        breakdown = reduced.breakdown()
    else:
        e2e = {"agent_iters_per_s": {"value": win.rate(),
                                     "unit": "agent-iter/s"},
               "setup_s": {"value": setup_s, "unit": "s"}}
        metrics = {m["name"]: e2e[m["name"]] for m in cell.end_to_end}
    gc.collect()
    judged, ok = check(cell, seed, first, reference)
    result = {"correct": ok, "attempted": win.iters, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in judged.items()}
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(load_cell(args.workload), args.seed, args.seconds,
                     bool(args.trace), t_start)
    except NoChip as e:
        print(f"perfbench: {e}; nothing measured", file=sys.stderr)
        return NO_CHIP_EXIT
    for name, v in result["checks"].items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
