"""One run of one cell: set-up, the measured window, the metrics, the check.

Set-up builds the configuration's App and the traffic's driver from the
seed, warms every shape the traffic uses and fills the caches.  The window
then runs the driver's closed loop for ``seconds`` with the program's
compile guard armed, and ends with a drain (``block_until_ready``) that
counts in the last driver tick.  With ``trace`` the window runs under the
JAX profiler and the per-layer metrics are read; without it, the
end-to-end ones.  After the window the device peak is read, the driver's
outputs are pulled to the host, the program's state is dropped, and the
plain reference is run over the same inputs to decide ``correct``.
"""

from __future__ import annotations

import contextlib
import gc
import shutil
import tempfile
import time

from . import spec
from .apps import build_app, seeded_values
from .compare import compare
from .nbytes import registered_state_bytes


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


class Marks:
    """Profiler annotations, or nothing while the profiler is off."""

    def __init__(self):
        self.on = False

    def __call__(self, name: str, step=None):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        if step is not None:
            return jax.profiler.StepTraceAnnotation(name, step_num=step)
        return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """Counts XLA backend compiles in this process (cache hits excluded)."""

    def __init__(self):
        import jax

        self.n = 0

        def on_event(event: str, duration: float, **kw) -> None:
            if event.endswith("backend_compile_duration"):
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)


class Run:
    """What the metric readers see of one run (``metrics/<name>.py``)."""

    def __init__(self, **fields):
        self.__dict__.update(fields)
        self._values = {}

    def value(self, name: str):
        """Another metric of this run, read by its own reader."""
        if name not in self._values:
            self._values[name] = spec.metric_reader(name)(self)
        return self._values[name]


def _device_line(used) -> dict:
    """The devices as JAX reports them, and the peak of the fullest chip
    the cell uses."""
    import jax

    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    d0 = jax.devices()[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": jax.device_count(), "memory_peak_bytes": peak}


def _reference_states(cell, states, seeded, dtype="float32") -> dict:
    """Reference entities for every (key, frame) in ``states``."""
    ref = cell.reference()
    players = cell.config["make_app"]["num_players"]
    out = {}
    groups = {}
    for key, frame, _ents, gen in states:
        gid = id(gen) if ref.USES_INPUTS else 0
        groups.setdefault(gid, (gen, []))[1].append((key, frame))
    for gen, members in groups.values():
        frames = [f for _, f in members]
        table = gen.table(max(frames), players) if ref.USES_INPUTS else None
        sim = ref.simulate(cell.config, frames, table, seeded, dtype)
        for key, f in members:
            out[(key, f)] = sim[f]
    return out


def check_outputs(cell, collected, seeded, control=None) -> dict:
    """Compare the driver's outputs with the reference.  Under the
    ``"reference"`` control, the reference computed in the configuration's
    ``control.reference_dtype`` stands in the program's place."""
    ref = cell.reference()
    states = collected["states"]
    want = _reference_states(cell, states, seeded)
    if control == "reference":
        got = _reference_states(cell, states, seeded,
                                cell.config["control"]["reference_dtype"])
    else:
        got = {(k, f): ents for k, f, ents, _ in states}
    mismatches, gap, bad = 0, 0.0, 0
    limit = cell.config["limits"]["state_err"]
    for key, f, _ents, _gen in states:
        m, g = compare(got[(key, f)], want[(key, f)], ref.FLOATS, ref.EXACT)
        mismatches += m
        gap = max(gap, g)
        bad += int(m > 0 or g > limit)
    return {"states": len(states), "bad_states": bad,
            "exact_mismatches": mismatches, "state_err": gap,
            "frames": sorted({f for _, f, _, _ in states})}


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_proc0: float, control=None,
             require_chip: bool = True, app_hook=None) -> dict:
    """Run one cell; returns the result object (without printing).

    ``control`` is None for the program as configured, ``"program"`` for
    the program with the configuration's lower-precision path switched on
    (``control.make_app``), ``"reference"`` for the reference computed in
    ``control.reference_dtype`` in the program's place.  ``app_hook(app)``
    may replace parts of the App before the driver is built: the fault
    tests plant their faults there."""
    import jax

    from bevy_ggrs_tpu import telemetry
    from bevy_ggrs_tpu.utils import compile_guard
    from bevy_ggrs_tpu.utils.compile_guard import RecompileError
    from bevy_ggrs_tpu.utils.platform import enable_compile_cache

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        raise NoDevice(
            f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX finds "
            f"{len(devices)} {devices[0].platform} device(s)")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileCounter()
    flight = telemetry.flight_recorder()
    telemetry.configure_flight(maxlen=4_000_000, enabled=True)

    cfg, traffic = cell.config, cell.traffic
    marks = Marks()
    if control not in (None, "program", "reference"):
        raise ValueError(f"unknown control {control!r}")
    app = build_app(cfg, seed, control=control == "program")
    if app_hook is not None:
        app_hook(app)
    state_bytes = registered_state_bytes(app.reg)
    drv = cell.driver().Driver(app, cfg, traffic, seed, marks)
    drv.warm()
    used = drv.devices

    guard = compile_guard.set_compile_guard(True)
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        marks.on = True
    ticks, recompile = [], None
    c0, f0 = drv.counters(), drv.session_frames()
    n_compiles0 = compiles.n
    flight.clear()
    gc.collect()  # set-up's garbage is not the window's to collect
    guard.arm(watch_jax=True)
    clock = time.perf_counter
    t_start = clock()
    setup_s = t_start - t_proc0
    with marks("window"):
        try:
            while clock() - t_start < seconds:
                drv.step(ticks, clock)
        except RecompileError as e:
            recompile = str(e)
        t = clock()
        drv.drain()
        if ticks:
            ticks[-1] += clock() - t
    window_s = clock() - t_start
    guard.disarm()
    compile_guard.set_compile_guard(False)
    steady_compiles = compiles.n - n_compiles0 + int(recompile is not None)
    flight_ticks = flight.snapshot("tick")
    c1, f1 = drv.counters(), drv.session_frames()
    summary = None
    if trace:
        marks.on = False
        jax.profiler.stop_trace()
        from .trace import load_events, reduce

        summary = reduce(load_events(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    device = _device_line(used)

    phase_s = {k: v - c0["phase_seconds"].get(k, 0.0)
               for k, v in c1["phase_seconds"].items()}
    run = Run(
        seconds=seconds, window_s=window_s, setup_s=setup_s,
        ticks=ticks, driver_ticks=len(ticks), session_ticks=f1 - f0,
        phase_s=phase_s,
        dispatches=c1["device_dispatches"] - c0["device_dispatches"],
        flight_ticks=flight_ticks,
        advances=sum(e.get("advances", 0) for e in flight_ticks),
        loads=sum(e.get("rollbacks", 0) for e in flight_ticks),
        state_bytes=state_bytes, trace=summary,
        device_kind=device["kind"], n_devices=len(used),
    )
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = run.value(m["name"])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    names = list(cell.reference().FLOATS) + list(cell.reference().EXACT)
    collected = drv.collect(names)
    drv.close()
    del drv, app
    gc.collect()
    seeded = seeded_values(cfg, seed)
    chk = check_outputs(cell, collected, seeded, control)
    limits = cfg["limits"]
    checks = [
        ("state_err", chk["state_err"], limits["state_err"], "<="),
        ("exact_mismatches", chk["exact_mismatches"], 0, "<="),
        ("oracle_failures", collected["oracle_failures"], 0, "<="),
        ("steady_compiles", steady_compiles, 0, "<="),
        ("states_compared", chk["states"], traffic["min_states"], ">="),
    ]
    ok = all(v <= lim if op == "<=" else v >= lim for _, v, lim, op in checks)
    result = {
        "correct": bool(ok),
        "attempted": chk["states"],
        "failed": chk["bad_states"] + collected["oracle_failures"],
        "metrics": metrics,
        "device": device,
    }
    if trace and summary is not None and summary["devices"]:
        busy = [d["busy_s"] for d in summary["devices"].values()][:len(used)]
        result["device"]["busy_s"] = sum(busy) / len(busy)
        result["device"]["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["info"] = {
        "seed": seed, "driver_ticks": len(ticks),
        "session_ticks": f1 - f0, "recompile": recompile,
        "rollbacks": run.loads, "frames_simulated": run.advances,
        "phase_ms_per_tick": {k: v * 1e3 / max(len(ticks), 1)
                              for k, v in sorted(phase_s.items())},
        "frames_compared": chk["frames"][:3] + chk["frames"][-3:],
    }
    result["checks"] = {
        name: {"value": v, "limit": lim, "must_be": op}
        for name, v, lim, op in checks
    }
    return result
