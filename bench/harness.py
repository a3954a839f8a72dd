"""Run one cell of the benchmark once: set up, warm up, measure, check.

Everything is found by name.  ``BENCHMARK.json`` names the cell, its
configuration (a file of sizes, which names its system under test in
``systems/``, its plain reference in ``references/`` and, by its
``model.cell``, its work count in ``work/``) and its traffic mix
(``traffic/<name>.json``, whose ``generator`` key names the module
``generators/<generator>.py`` that reads it); every metric is a reader
``metrics/<name>.py`` with ``read(ctx) -> float | None``.  A later cell,
configuration, mix, generator or metric is a new file and a new entry,
never an edit here.

A generator module has a class ``Generator(mix, model, seed_seq)``, where
``model`` is the configuration's ``model`` section, with ``capacity`` (the
most streams attached at once) and ``max_buffered`` (samples a stream
holds at most), which size the system, ``rollin_ticks``, ``check_ids``
(the sampled streams), ``setup(system)`` (attach before warm-up),
``prepare(tick)`` (made outside the timed tick), ``drive(system,
prepared)`` (feeds, attaches, detaches: inside it) and
``expected(last_tick, emitted)``, which maps (stream id, step) of every
prediction the sampled streams were due to emit once ``last_tick`` has
run to the reference's input for it.  ``emitted`` maps (stream id, step)
to what the program chose there (see the system's ``step``), so the input
of a sequence's step k can be its prompt and the program's first k tokens.

A system module has a class ``System(cfg, params, *, slots, ring, bits,
trace)`` whose ``step()`` returns the tick's emitted batches as
(stream_ids, steps, logits) or (stream_ids, steps, logits, choices), the
choice being what the program picked from each row (an emitted token).
A reference module has ``make_params(cfg, seed_seq)`` and a class
``Reference(cfg, params)`` whose ``logits(inputs)`` takes the expected
inputs as a list in key order and returns one row of logits for each.
A work module has ``count(model, counters) -> {"flops", "hbm_bytes"}``:
the operations and bytes the window's work needs, from the model section
and the counters' deltas over the window (``counters["stream_steps"]``
among them); readers get it as ``ctx["work"]``.

One tick: the generator drives the system (``bench.feed``), the system
steps once (``bench.step``), and the host waits until the tick's device
work is done (``bench.sync``).  Set-up rolls the streams in and runs a few
ticks more (past a window, for a windowed model), so every shape the
window uses is compiled before it opens.
A closed loop starts each tick when the previous one is done and its
inputs are prepared; an open loop starts tick k at t0 + k / tick_hz, or
late, never skipped.  A tick's latency runs from when it was due to when
its device work is done.  The system's time in a closed loop is the sum
of its ticks' latencies: the window less the generator's preparation.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import tempfile
import time

import numpy as np

import check
import trace_reduce

WARM_TICKS = 4          # past the roll-in and the first window's end
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def _load_module(path: str):
    name = "bench_" + os.path.relpath(path).replace(os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _CompileCount:
    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1


class Bench:
    """The benchmark under ``root`` (the checkout that holds BENCHMARK.json).
    ``search`` lists further directories searched first for a mix, metric,
    system or reference of a given name."""

    def __init__(self, root: str, spec: dict | None = None, search=()):
        self.root = root
        if spec is None:
            with open(os.path.join(root, "BENCHMARK.json")) as f:
                spec = json.load(f)
        self.spec = spec
        self.dirs = [*search, *(os.path.join(root, p) for p in spec["paths"])]

    def find(self, kind: str, name: str, ext: str) -> str:
        for d in self.dirs:
            path = os.path.join(d, kind, name + ext)
            if os.path.isfile(path):
                return path
        raise FileNotFoundError(f"no {kind}/{name}{ext} under {self.dirs}")

    def _entry(self, key: str, name: str) -> dict:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"BENCHMARK.json has no {key} entry {name!r}")

    def metrics_of(self, workload: str, kind: str) -> list:
        return [m for m in self.spec[kind]
                if workload in m.get("workloads", [workload])]

    def load_cell(self, workload: str) -> tuple[dict, dict, dict]:
        wl = self._entry("workloads", workload)
        with open(os.path.join(self.root, self._entry("configs", wl["config"])["file"])) as f:
            cfg = json.load(f)
        with open(self.find("traffic", wl["traffic"], ".json")) as f:
            mix = json.load(f)
        return wl, cfg, mix

    def run(self, workload: str, seed: int, seconds: float, trace: bool, *,
            t_start: float, require_chip: bool = True, bits: int | None = None,
            mix: dict | None = None, trace_dir: str | None = None,
            keep_latencies: bool = False) -> dict:
        """One run of one cell; the result line as a dict.  ``bits`` swaps
        the program's weight precision (the control); ``mix`` replaces the
        cell's traffic (the knee sweep); ``trace_dir`` keeps the profile
        there instead of in a temporary directory; ``keep_latencies`` adds
        every tick's latency in seconds under ``latencies_s``."""
        import jax
        wl, cfg, cell_mix = self.load_cell(workload)
        mix = mix or cell_mix
        devices = jax.devices()
        if require_chip and (devices[0].platform != "tpu"
                             or len(devices) < wl["chips"]):
            raise NoChip(f"cell {workload} needs {wl['chips']} TPU chip(s); JAX "
                         f"found {len(devices)} {devices[0].platform} device(s)")
        from repro.kernels import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        compiles = _CompileCount()

        model_ss, traffic_ss = np.random.SeedSequence(seed % 2**64).spawn(2)
        ref_mod = _load_module(self.find("references", cfg["reference"], ".py"))
        sys_mod = _load_module(self.find("systems", cfg["serving"]["system"], ".py"))
        work_mod = _load_module(self.find("work", cfg["model"]["cell"], ".py"))
        params = ref_mod.make_params(cfg, model_ss)
        gen_mod = _load_module(self.find("generators", mix["generator"], ".py"))
        traffic = gen_mod.Generator(mix, cfg["model"], traffic_ss)
        ring = 1 << max(traffic.max_buffered, 1).bit_length()
        system = sys_mod.System(cfg, params, slots=traffic.capacity, ring=ring,
                                bits=bits, trace=trace)
        traffic.setup(system)
        log: list = []
        tick = 0
        annot = jax.profiler.TraceAnnotation if trace else (
            lambda _name: contextlib.nullcontext())

        def run_tick(batch):
            nonlocal tick
            with annot("bench.feed"):
                traffic.drive(system, batch)
            with annot("bench.step"):
                log.extend(system.step())
            with annot("bench.sync"):
                system.sync()
            tick += 1

        for _ in range(max(traffic.rollin_ticks, cfg["model"].get("window", 0))
                       + WARM_TICKS):
            run_tick(traffic.prepare(tick))
        setup_s = time.perf_counter() - t_start

        c0, s0, n_compiles = system.counters(), system.span_totals(), compiles.n
        if trace:
            tmp = tempfile.TemporaryDirectory() if trace_dir is None else None
            prof_dir = trace_dir or tmp.name
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(prof_dir, profiler_options=opts)
        lat, gen_s = [], 0.0
        with annot("bench.window"):
            t0 = done = time.perf_counter()
            if mix["loop"] == "closed":
                while done - t0 < seconds:
                    g = time.perf_counter()
                    batch = traffic.prepare(tick)
                    due = time.perf_counter()
                    gen_s += due - g
                    run_tick(batch)
                    done = time.perf_counter()
                    lat.append(done - due)
            else:
                period = 1.0 / mix["tick_hz"]
                for k in range(max(1, round(seconds * mix["tick_hz"]))):
                    g = time.perf_counter()
                    batch = traffic.prepare(tick)
                    gen_s += time.perf_counter() - g
                    due = t0 + k * period
                    while (wait := due - time.perf_counter()) > 0:
                        time.sleep(wait if wait > 2e-3 else 0)
                    run_tick(batch)
                    done = time.perf_counter()
                    lat.append(done - due)
        t1 = done
        reduced = None
        if trace:
            jax.profiler.stop_trace()
        c1, s1 = system.counters(), system.span_totals()
        in_window = compiles.n - n_compiles
        if trace:
            path = trace_reduce.find_xplane(prof_dir)
            reduced = trace_reduce.reduce(path) if path else None
            if tmp is not None:
                tmp.cleanup()
        used = devices[:wl["chips"]]
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in used]
        system.close()
        del system

        # the check: after the window, with the program's state freed
        reference = ref_mod.Reference(cfg, params)
        got, chosen = check.collect(log, traffic.check_ids)
        nums = check.numbers(got, traffic.expected(tick - 1, chosen), reference,
                             cfg["check"], chosen)
        correct, checks, failed = check.verdict(nums, cfg["check"])
        counters = {k: c1[k] - c0[k] for k in c1}

        ctx = {
            "setup_s": setup_s, "window_s": t1 - t0, "ticks": len(lat),
            "system_s": float(np.sum(lat)) if mix["loop"] == "closed" else t1 - t0,
            "latencies_s": np.asarray(lat), "generator_s": gen_s,
            "stream_steps": counters["stream_steps"], "counters": counters,
            "spans": {k: v - s0.get(k, 0.0) for k, v in s1.items()},
            "trace": reduced, "work": work_mod.count(cfg["model"], counters),
            "peak": self.peak(used[0].device_kind) if require_chip else None,
            "chips": wl["chips"],
        }
        kind = "per_layer" if trace else "end_to_end"
        metrics = {}
        for m in self.metrics_of(workload, kind):
            value = _load_module(self.find("metrics", m["name"], ".py")).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        d = devices[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(devices),
                  "memory_peak_bytes": max((p for p in peaks if p), default=0)}
        out = {"correct": bool(correct), "attempted": nums["due"],
               "failed": failed,
               "metrics": metrics, "device": device}
        if reduced is not None and reduced["devices"]:
            device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            out["breakdown"] = {"device_ops": reduced["top_ops"],
                                "idle_gaps": reduced["top_gaps"]}
        out["run"] = {"seed": seed, "ticks_in_window": len(lat),
                      "compiles_in_window": in_window,
                      "generator_ms_per_tick": 1e3 * gen_s / max(len(lat), 1),
                      "tick_ms": {f"p{p}": float(np.percentile(lat, p)) * 1e3
                                  for p in (50, 99, 100)} if lat else None,
                      "predictions_compared": len(nums["gaps"])}
        if keep_latencies:
            out["latencies_s"] = lat
        out["checks"] = checks
        return out

    def peak(self, device_kind: str) -> dict:
        with open(self.find("", "peaks", ".json")) as f:
            table = json.load(f)["devices"]
        if device_kind not in table:
            raise KeyError(f"no published peaks for device kind {device_kind!r}")
        return table[device_kind]


def print_checks(result: dict, stream=sys.stderr) -> None:
    """Each compared number beside its limit, as the last lines of stderr."""
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=stream)
    print(f"correct {result['correct']}", file=stream, flush=True)
