"""One run of one cell: set-up, the measured window, the comparison, and,
when traced, the per-layer metrics.

Everything of one cell is found by name: ``BENCHMARK.json`` names the
cell's configuration (``configs/<config>.json``) and traffic
(``traffic/<traffic>.json``); the traffic names its entry
(``entries/<entry>.py``); ``workloads/<cell>.json`` holds the limits of
the comparison; each per-layer metric is read by ``metrics/<metric>.py``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

from chipbench.lib import hlo, intervals, peaks, reference, trace

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parent
#: Warm-up calls after the compile, before the window.
WARMUP_CALLS = 3


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    entry: object
    end_to_end: list
    per_layer: list


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(f"chipbench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    cfg_file = {c["name"]: c["file"] for c in bench["configs"]}[w["config"]]
    return make_cell(
        name,
        int(w["chips"]),
        root / cfg_file,
        w["traffic"],
        [m for m in bench["end_to_end"] if _applies(m, name)],
        [m for m in bench["per_layer"] if _applies(m, name)],
    )


def make_cell(name, chips, config_file, traffic, end_to_end, per_layer) -> Cell:
    """A cell from its files: the configuration, ``traffic/<traffic>.json``
    (which names the entry) and ``workloads/<name>.json`` (its limits)."""
    mix = json.loads((HERE / "traffic" / f"{traffic}.json").read_text())
    return Cell(
        name=name,
        chips=chips,
        config=json.loads(pathlib.Path(config_file).read_text()),
        traffic=mix,
        limits=json.loads((HERE / "workloads" / f"{name}.json").read_text())["limits"],
        entry=load_module(HERE / "entries" / f"{mix['entry']}.py"),
        end_to_end=end_to_end,
        per_layer=per_layer,
    )


class CompileCounter:
    """Counts JAX's trace, lowering and compile events while armed."""

    def __init__(self, jax):
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if self.armed and event.startswith("/jax/core/compile/"):
            self.count += 1


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _percentile(sorted_vals, q: float) -> float:
    return float(np.quantile(np.asarray(sorted_vals), q))


def run(
    jax,
    cell: Cell,
    seed: int,
    seconds: float,
    traced: bool,
    devices,
    t_start: float,
    wrap=None,
    save_trace=None,
) -> dict:
    """One run of ``cell``; returns the result object.  ``wrap``, where
    given, takes the entry's jitted callable and returns the one the window
    drives (the tests break the timed path with it)."""
    from repro.core import faults

    cfg, traffic, entry = cell.config, cell.traffic, cell.entry
    if (traffic["loop"], traffic["in_flight"]) != ("closed", 1):
        raise ValueError("the harness drives a closed loop with one call in flight")
    devices = list(devices)[: cell.chips]
    counter = CompileCounter(jax)

    phases = {"start_s": time.perf_counter() - t_start}
    built = entry.build(jax, cfg, traffic, seed, devices)
    fn = built["fn"] if wrap is None else wrap(built["fn"], built)
    args = built["args"]
    note = built["note"]
    jax.block_until_ready(args)
    phases["build_s"] = time.perf_counter() - t_start
    compiled = fn.lower(*args).compile()
    phases["compile_s"] = time.perf_counter() - t_start
    for p in built["plans"]:
        if p.backend.name != "pallas":
            raise RuntimeError(f"negotiation picked {p.backend.name!r}, not 'pallas'")
    hlo_text = compiled.as_text()
    out = None
    for _ in range(WARMUP_CALLS):
        out = None  # a pipeline frees one batch's output before the next call
        out = jax.block_until_ready(compiled(*args))
    degraded = len(faults.degradation_log())
    setup_s = time.perf_counter() - t_start

    # -- the window: one call in flight, each timed dispatch to ready -------
    tdir = tempfile.mkdtemp(prefix="chipbench_trace_") if traced else None
    span = jax.profiler.TraceAnnotation if traced else (lambda _n: contextlib.nullcontext())
    lat: list = []
    failed = 0
    counter.armed = True
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the harness's own spans only
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tdir, profiler_options=opts)
    t_w0 = time.perf_counter()
    t1 = t_w0
    try:
        while t1 - t_w0 < seconds:
            out = None
            t0 = time.perf_counter()
            with span("call"):
                with span("dispatch"):
                    out = compiled(*args)
                with span("wait"):
                    jax.block_until_ready(out)
            t1 = time.perf_counter()
            lat.append(t1 - t0)
    except Exception as err:  # a call that raises fails, and ends the window
        failed += 1
        out = None
        _say(f"chipbench: call failed: {type(err).__name__}: {err}")
        t1 = time.perf_counter()
    finally:
        if traced:
            jax.profiler.stop_trace()
        counter.armed = False
    window_s = t1 - t_w0
    compiles = counter.count
    attempted = len(lat) + failed
    if degraded:
        failed = attempted  # every call ran a program with a leaf demoted to XLA

    stats = [d.memory_stats() or {} for d in devices]
    mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    # -- the comparison, after the window; the program's state freed first --
    sel = entry.picks(cfg, traffic, seed)
    got = host_in = None
    if out is not None:
        got, host_in = entry.answers(out, args, sel)
    del out, args, compiled, built, fn
    err_rel = float("inf")
    if got is not None:
        ref = entry.reference(host_in, cfg, traffic)
        err_rel = reference.max_err_rel(got, ref)
        del ref, got, host_in

    checks = {
        "max_err_rel": {"value": err_rel, "limit": cell.limits["max_err_rel"]},
        "failed_calls": {"value": failed, "limit": 0},
        "compiles_in_window": {"value": compiles, "limit": 0},
    }
    correct = attempted > 0 and all(c["value"] <= c["limit"] for c in checks.values())

    d0 = devices[0]
    device = {
        "platform": d0.platform,
        "kind": d0.device_kind,
        "count": len(devices),
        "memory_peak_bytes": mem_peak,
    }
    info = {
        "cell": cell.name,
        "seed": seed,
        "calls": len(lat),
        "window_s": window_s,
        "kernels": hlo.count_kernels(hlo_text),
        "all_to_all": hlo.count_all_to_all(hlo_text),
        "memory_peak_bytes": mem_peak,
        "degradations": degraded,
        "call_ms_median": 1e3 * _percentile(sorted(lat), 0.5) if lat else None,
        "setup_phases_s": phases,
        "samples_per_call": entry.samples(cfg, traffic),
        "essential": entry.essential(cfg, traffic),
        **note,
    }
    print(json.dumps(info), flush=True)

    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if traced:
        tr = compact_trace(
            trace.read(trace.latest_xplane(tdir), [d.id for d in devices]),
            hlo_text, cell, len(lat), peaks.lookup(d0.device_kind),
        )
        shutil.rmtree(tdir, ignore_errors=True)
        if save_trace:
            pathlib.Path(save_trace).write_text(json.dumps(tr))
        result["metrics"] = per_layer_metrics(cell, tr)
        busy = [intervals.length(intervals.union(intervals.spans(ev), *tr["window_ns"]))
                for ev in tr["devices"]]
        device["busy_s"] = 1e-9 * sum(busy) / len(busy)
        device["window_s"] = 1e-9 * (tr["window_ns"][1] - tr["window_ns"][0])
        result["device"] = device
        result["breakdown"] = breakdown(tr)
    else:
        samples = cell.entry.samples(cfg, traffic)
        values = {
            "setup_s": setup_s,
            "msamples_per_s": samples * len(lat) / window_s / 1e6 if lat else 0.0,
        }
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in cell.end_to_end
        }
        result["device"] = device
    result["checks"] = checks
    return result


def compact_trace(raw: dict, hlo_text: str, cell: Cell, calls: int, pk: dict) -> dict:
    """The compact trace of :mod:`chipbench.lib.trace` from the raw events."""
    calls_spans = [e for e in raw["host"] if e[0] == "call"]
    if calls_spans:
        lo = calls_spans[0][1]
        hi = max(s + d for _, s, d in calls_spans)
    else:  # no host spans: the device's own first and last operation
        flat = [e for ev in raw["devices"] for e in ev]
        lo = min(s for _, s, _ in flat)
        hi = max(s + d for _, s, d in flat)
    inside = lambda evs: [e for e in evs if e[1] >= lo and e[1] < hi]  # noqa: E731
    return {
        "window_ns": [lo, hi],
        "calls": calls,
        "chips": cell.chips,
        "essential": cell.entry.essential(cell.config, cell.traffic),
        "peaks": {k: pk[k] for k in ("flops_per_s", "hbm_bytes_per_s")},
        "ops": hlo.classify(hlo_text),
        "devices": [inside(ev) for ev in raw["devices"]],
        "host": raw["host"],
        "labels": raw["labels"],
    }


def per_layer_metrics(cell: Cell, tr: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        value = load_module(HERE / "metrics" / f"{m['name']}.py").reduce(tr)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(tr: dict, top: int = 10) -> dict:
    """The device operations that took most time (seconds per device), and
    the longest idle stretches named by the harness span around them."""
    labels = tr.get("labels", {})
    per_op: dict = {}
    for ev in tr["devices"]:
        for name, _s, d in ev:
            key = f"{name} {labels.get(name, '')}".strip()
            per_op[key] = per_op.get(key, 0.0) + d * 1e-9 / len(tr["devices"])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    lo, hi = tr["window_ns"]
    host = tr["host"]
    gaps = []
    for ev in tr["devices"]:
        for s, e in intervals.gaps(intervals.union(intervals.spans(ev), lo, hi), lo, hi):
            mid = (s + e) / 2
            around = [n for n, hs, hd in host if hs <= mid < hs + hd and n != "call"]
            gaps.append([around[-1] if around else "between calls", (e - s) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [list(kv) for kv in ops], "idle_gaps": gaps[:top]}
