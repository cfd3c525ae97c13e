"""Runs one cell of BENCHMARK.json once and returns the contract's line.

Knows no cell, configuration, traffic mix, metric or model family by
name: the cell names a configuration file and a traffic file, the
per-layer metrics name a reader under ``layers/``, and the configuration
names its reference module (the adapter: the state the program's step
takes, which of its leaves are compared, the draws, the reference's
batches; ``sage_reference.py`` states the protocol) and its cost function
(edges, FLOPs and bytes a step). Of the program's ``state`` the harness
knows one key, ``consts``: the tables it builds and hands over.

What the window drives is the program's own entry: flags parsed by
``run_loop.define_flags()`` over the preset defaults, ``build_graph`` ->
``build_model`` -> ``make_mesh`` -> ``euler_tpu.train.train()`` as
``run_loop.run_train`` calls it. The harness adds a ``step_hook`` (the
timestamps, the window, and what the first three steps produced), hands
``train()`` weights it made itself from ``--seed`` (``state=``), and
leaves it by an exception raised from the hook once the window closed.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import logging
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CAPTURED_STEPS = 3
log = logging.getLogger("benchmark")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class _WindowClosed(Exception):
    """Raised from the step hook to leave train() after the window."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its files resolved."""

    def __init__(self, manifest_path: str, workload: str):
        self.manifest_path = os.path.abspath(manifest_path)
        self.root = os.path.dirname(self.manifest_path)
        self.manifest = load_json(self.manifest_path)
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in {manifest_path}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.manifest["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.cfg = load_json(os.path.join(self.root, self.config_entry["file"]))
        self.traffic = load_json(
            os.path.join(HERE, "traffic", self.entry["traffic"] + ".json")
        )
        self.global_batch = int(self.cfg["batch_size"]) * (
            self.chips if self.traffic.get("batch_scale") == "per_chip" else 1
        )

    def metrics(self, kind: str) -> list:
        """The cell's metrics of one kind (``end_to_end``/``per_layer``)."""
        return [
            m for m in self.manifest[kind]
            if "workloads" not in m or self.name in m["workloads"]
        ]

    def argv(self, data_dir: str) -> list:
        mod, attr = self.cfg["preset"]
        preset = list(getattr(importlib.import_module(mod), attr))
        flags = dict(self.cfg["flags"])
        flags.update(self.traffic["flags"])
        flags["data_dir"] = data_dir
        flags["batch_size"] = self.global_batch
        flags["num_devices"] = self.chips
        out = preset
        for k, v in flags.items():
            out += ["--" + k, str(v)]
        return out


def roots_for_step(seed: int, step: int, num_nodes: int, batch: int):
    """The root-node stream: uniform over the nodes, a function of
    (--seed, step) so that every run of a seed trains on the same rows."""
    rng = np.random.default_rng([seed, step])
    return rng.integers(0, num_nodes, batch, dtype=np.int64)


class Hook:
    """The harness's ``step_hook``: reads ``train()``'s frame (its state,
    its current batch and loss; the program hands the hook only the step
    number), keeps what the first steps produced, opens the window after
    warm-up on a fenced step boundary, stamps every step, and closes the
    window on a fence after the first step that ends past ``seconds``."""

    def __init__(self, cell: Cell, ref, model, seconds: float,
                 trace_span=None, recorder=None,
                 first_steps_only: bool = False):
        self.cell, self.ref, self.model = cell, ref, model
        self.first_steps_only = first_steps_only
        self.consts = None
        self.seconds = float(seconds)
        self.warmup = int(cell.traffic["warmup_steps"])
        self.trace_span = trace_span
        self.recorder = recorder
        self.captured = {"hops": [], "losses": []}
        self.first_step_compile = None
        self.stamps: list = []
        self.t_open = self.t_close = None
        self.at_open = self.at_close = None
        self.steps_in_window = 0

    @staticmethod
    def _locals(frame):
        loc = frame.f_locals
        missing = [k for k in ("state", "batch", "last_loss") if k not in loc]
        if missing:
            raise RuntimeError(
                "benchmark hook: train() no longer keeps "
                f"{missing} as locals where step_hook is called; the "
                "first-steps comparison reads them there"
            )
        return loc

    def _snapshot(self):
        from euler_tpu import devprof, telemetry

        data = telemetry.telemetry_json()
        return {
            "phases": {
                k: (h["count"], h["sum_us"])
                for k, h in telemetry.phase_hists(data).items()
            },
            "compiles": devprof.compile_summary(data)["compile_events"],
        }

    @staticmethod
    def _stop_recorder():
        """Take the recorder out as the program's span sink. Its own
        ``stop()`` compares two bound methods by identity and so never
        does (the program's to mend: PERF.md section 7)."""
        from euler_tpu import telemetry

        telemetry.set_trace_sink(None)

    def _capture(self, step: int, loc: dict):
        import jax

        from euler_tpu import devprof

        if step == 1:
            self.first_step_compile = devprof.compile_summary()
        state, batch = loc["state"], loc["batch"]
        self.captured["losses"].append(float(loc["last_loss"]))
        hops = self.ref.drawn_hops(self.model, state, batch)
        self.captured["hops"].append(
            [np.asarray(jax.device_get(h)) for h in hops]
        )
        if step == 1:
            self.captured["grad1"] = self.ref.first_gradient(state)
        if step == CAPTURED_STEPS:
            self.captured["end"] = self.ref.compared_state(state)

    def __call__(self, step: int):
        import jax

        if step <= CAPTURED_STEPS:
            loc = self._locals(sys._getframe(1))
            self._capture(step, loc)
            if step == CAPTURED_STEPS and self.first_steps_only:
                self.consts = loc["state"].get("consts")
                raise _WindowClosed()
            return
        if step < self.warmup:
            return
        if step == self.warmup:
            jax.block_until_ready(
                self._locals(sys._getframe(1))["last_loss"])
            self.at_open = self._snapshot()
            if self.recorder is not None:
                self.recorder.start()
            self.t_open_wall = time.time()
            self.cpu_open = (time.process_time(), time.thread_time())
            self.t_open = time.perf_counter()
            self.stamps.append(self.t_open)
            return
        now = time.perf_counter()
        self.stamps.append(now)
        if self.recorder is not None and step == self.trace_span[1] + 2:
            # the capture has ended and the spans of its steps are in.
            # The recorder is a ring of 200,000 events, about ten a step:
            # left on, a window of 20,000 steps pushes the traced steps'
            # spans out of it (seen on the chip at 51 s, PR 29)
            self._stop_recorder()
        if now - self.t_open < self.seconds:
            return
        if self.trace_span is not None and step <= self.trace_span[1] + 1:
            return  # the capture has to end inside the window
        loc = self._locals(sys._getframe(1))
        jax.block_until_ready(loc["last_loss"])
        self.t_close = time.perf_counter()
        self.cpu_s = (time.process_time() - self.cpu_open[0],
                      time.thread_time() - self.cpu_open[1])
        self.consts = loc["state"].get("consts")
        self.stamps[-1] = self.t_close
        self.steps_in_window = step - self.warmup
        if self.recorder is not None:
            self._stop_recorder()
        self.at_close = self._snapshot()
        raise _WindowClosed()


class Context:
    """What a per-layer reader may read. Each reader returns a number,
    or None where it finds nothing to read in this run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self._capture = None

    def phase(self, name: str):
        """(count, sum_us) of an eg_phase histogram over the window."""
        c1, s1 = self.at_close["phases"].get(name, (0, 0))
        c0, s0 = self.at_open["phases"].get(name, (0, 0))
        return c1 - c0, s1 - s0

    def phase_mean_ms(self, name: str):
        count, total = self.phase(name)
        return total / count / 1e3 if count > 0 else None

    def device_ms_per_step(self, pattern):
        """Device time per traced step of the ops whose trace name
        matches ``pattern``, fullest chip; None where there are none."""
        cap = self.capture
        if cap is None:
            return None
        secs = sum(cap.fullest().op_seconds(pattern).values())
        return secs * 1e3 / self.trace_steps if secs > 0 else None

    @property
    def capture(self):
        if self._capture is None and self.xplane_path:
            from benchmark import xplane

            self._capture = xplane.read_capture(self.xplane_path)
        return self._capture


def _require_devices(chips: int, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform == "cpu":
        raise NoChip("JAX found no accelerator (platform cpu)")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


class Prepared:
    """The part of set-up that does not depend on ``--seed``: the graph
    files, the loaded engine, the mesh, the model and its device tables.
    ``run_cell`` makes one and drives it once; the calibration drives
    one over many seeds (the tables come back out of each ``train()``)."""

    def __init__(self, manifest_path: str, workload: str, t_start: float,
                 require_chip: bool = True, data_root: str | None = None):
        self.cell = cell = Cell(manifest_path, workload)
        self.cfg = cfg = cell.cfg
        self.devices = _require_devices(cell.chips, require_chip)

        from benchmark import graphgen
        from euler_tpu import devprof, run_loop
        from euler_tpu.graph import device as device_graph
        from euler_tpu.parallel import (
            enable_compile_cache,
            make_mesh,
            pad_tables_for_mesh,
            put_global,
            state_sharding,
        )

        logging.basicConfig(
            level=logging.INFO, format="%(asctime)s %(name)s %(message)s"
        )
        logging.getLogger("absl").setLevel(logging.WARNING)
        log.info("compile cache: %s", enable_compile_cache() or "off")
        devprof.setup(enabled=True, sample_ms=1000)
        self.marks = {"imports": time.time() - t_start}

        # the graph: a function of the configuration, cached on disk
        self.spec = spec = graphgen.spec_from_config(cfg)
        data_root = data_root or os.path.join(ROOT, ".data", "benchmark")
        data_dir = spec.write(os.path.join(
            data_root, "%s_n%d_s%d" % (cell.entry["config"], spec.num_nodes,
                                       spec.graph_seed)))
        self.marks["graph_files"] = time.time() - t_start
        self.args = args = run_loop.define_flags().parse_args(
            cell.argv(data_dir))
        run_loop.check_serve_flags(args)
        self.graph, self.services = run_loop.build_graph(args)
        self.marks["graph_load"] = time.time() - t_start
        self.mesh = make_mesh(
            args.num_devices, model_parallel=args.model_parallel)
        self.ref = load_module(
            os.path.join(cell.root, cfg["reference"]),
            "benchmark_reference_" + cell.entry["config"])
        with device_graph.kernel_mesh_scope(self.mesh):
            self.model = run_loop.build_model(args, self.graph)
            # the tables go onto the mesh here, by the program's own
            # placement rules, so that the arrays train() is handed are
            # the ones its step donates (train()'s own put is then a
            # no-op) and no second copy of them stays referenced
            tables = pad_tables_for_mesh(
                {"consts": self.model.build_consts(self.graph)}, self.mesh)
            self.consts = put_global(
                tables, state_sharding(self.mesh, tables))["consts"]
            del tables
        self.marks["tables"] = time.time() - t_start

    def drive(self, seed: int, seconds: float, trace_dir: str | None = None,
              first_steps_only: bool = False) -> Hook:
        """One ``train()`` call from ``--seed``: the state the reference
        makes from it (weights in one jitted call), the root stream, the
        hook. Returns the hook with
        what it took; ``self.consts`` is what train() handed back."""
        import jax

        from euler_tpu import train as train_lib

        cell, cfg, args, ref = self.cell, self.cfg, self.args, self.ref
        key = jax.random.fold_in(
            jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
        opt = train_lib.get_optimizer(args.optimizer, args.learning_rate)
        start, state = ref.init_state(cfg, key, opt)
        state["consts"] = self.consts
        self.consts = None
        trace_span = recorder = None
        if trace_dir:
            from euler_tpu.trace import TraceRecorder

            a = int(cell.traffic["warmup_steps"]) + int(
                cell.traffic["trace_offset_steps"])
            trace_span = (a, a + int(cell.traffic["trace_steps"]))
            recorder = TraceRecorder()
        hook = Hook(cell, ref, self.model, seconds, trace_span, recorder,
                    first_steps_only=first_steps_only)
        hook.captured["start"] = {
            k: np.asarray(v) for k, v in start.items()}
        num_nodes, batch = self.spec.num_nodes, cell.global_batch

        def source_fn(step):
            return roots_for_step(seed, step, num_nodes, batch)

        try:
            train_lib.train(
                self.model, self.graph, source_fn,
                num_steps=10**9,
                optimizer=args.optimizer,
                learning_rate=args.learning_rate,
                mesh=self.mesh,
                log_every=args.log_steps,
                seed=seed & 0x7FFFFFFF,
                prefetch_depth=args.prefetch_depth,
                prefetch_threads=args.prefetch_threads,
                sampler_depth=args.sampler_depth,
                checkpoint_dir=args.model_dir or None,
                profile_dir=trace_dir,
                **({"profile_steps": trace_span} if trace_span else {}),
                step_hook=hook,
                state=state,
            )
            raise RuntimeError("train() returned before the window closed")
        except _WindowClosed:
            pass
        self.consts = hook.consts
        hook.consts = None
        # the frames of train() went with the exception: its prefetch
        # generator is finalised, and its workers stopped, here
        gc.collect()
        return hook

    def close(self):
        from euler_tpu import devprof

        self.consts = None
        devprof.stop_sampler()
        for s in self.services:
            s.stop()
        gc.collect()

    def compare(self, hook, **kw) -> dict:
        from benchmark import check

        return check.compare(self.cfg, self.spec, self.ref, hook.captured,
                             **kw)


def run_cell(manifest_path: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, require_chip: bool = True,
             calibrate: bool = False, data_root: str | None = None,
             keep_trace: str | None = None) -> dict:
    """One run. Returns the result line as a dict (see the contract).

    ``calibrate`` adds, under ``calibration``, the numbers of the control
    (the reference in bfloat16 in the program's place) and of the planted
    faults (the reference on half, and on one chip's share, of the batch):
    the readings the limits are set from. Benchmark runs never ask for it.
    """
    from benchmark import check, costs, peaks, xplane

    prep = Prepared(manifest_path, workload, t_start, require_chip,
                    data_root)
    cell, cfg, devices = prep.cell, prep.cfg, prep.devices
    profile_dir = None
    if trace:
        profile_dir = keep_trace or os.path.join(
            ROOT, ".data", "benchmark", "trace_%s" % cell.name)
        if os.path.isdir(profile_dir):
            shutil.rmtree(profile_dir)
    hook = prep.drive(seed, seconds, profile_dir)
    setup_s = hook.t_open_wall - t_start
    window_s = hook.t_close - hook.t_open
    steps = hook.steps_in_window
    compiles_in_window = (
        hook.at_close["compiles"] - hook.at_open["compiles"])
    memory_peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    )
    # the program's state is freed before the reference runs
    prep.close()

    # ---- correctness: the first steps against the plain reference ----
    t_check = time.time()
    numbers = prep.compare(hook)
    numbers["compiles_in_window"] = float(compiles_in_window)
    limits = dict(cfg["limits"])
    limits["compiles_in_window"] = 0
    correct, table = check.verdict(numbers, limits)
    check_s = time.time() - t_check

    dev0 = devices[0]
    is_chip = dev0.platform != "cpu"
    device = {
        "platform": dev0.platform,
        "kind": dev0.device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(memory_peak),
    }
    # what a step needs by shape, per chip: the configuration's own
    # cost function says (the sampled edges are its too)
    step_costs = costs.step_costs(
        cfg, cell.global_batch // cell.chips,
        str(cell.traffic["flags"].get("device_sampling")) == "true",
        root=cell.root)
    values = {
        "edges_per_s_chip": steps * step_costs["edges"] / window_s,
        "setup_s": setup_s,
    }
    stamps = np.asarray(hook.stamps)
    step_ms = np.diff(stamps) * 1e3
    result = {
        "correct": bool(correct),
        "attempted": int(steps),
        "failed": 0,
        "metrics": {},
        "device": device,
    }
    if not trace:
        # a CPU run (the tests) is no measurement: nothing under a
        # metric's name
        for m in cell.metrics("end_to_end") if is_chip else ():
            result["metrics"][m["name"]] = {
                "value": float(values[m["name"]]), "unit": m["unit"]}
    else:
        ctx = Context(
            cell=cell, cfg=cfg, chips=cell.chips, steps=steps,
            window_s=window_s,
            step_ms=step_ms,
            at_open=hook.at_open, at_close=hook.at_close,
            xplane_path=xplane.latest_xplane(profile_dir) if is_chip
            else None,
            trace_steps=hook.trace_span[1] - hook.trace_span[0],
            costs=step_costs,
            peaks=peaks.chip_peaks(dev0.device_kind) if is_chip else None,
            memory_peak_bytes=memory_peak,
            first_step_compile=hook.first_step_compile,
            phase_events=hook.recorder.events(),
        )
        for m in cell.metrics("per_layer"):
            reader = load_module(
                os.path.join(HERE, "layers", m["name"] + ".py"),
                "benchmark_layer_" + m["name"].replace(".", "_"))
            value = reader.read(ctx)
            if value is None:
                continue
            if is_chip:
                result["metrics"][m["name"]] = {
                    "value": float(value), "unit": m["unit"]}
            else:
                result.setdefault("withheld_cpu", []).append(m["name"])
        if keep_trace:
            with open(os.path.join(keep_trace, "phase_events.json"), "w") as f:
                json.dump(ctx.phase_events, f)
        cap = ctx.capture
        if cap is not None:
            device["busy_s"] = cap.busy_s
            device["window_s"] = cap.window_s
            result["breakdown"] = {
                "device_ops": cap.top_ops(10),
                "idle_gaps": cap.idle_by_host_phase(ctx.phase_events, 10),
            }
    result["setup_marks_s"] = {k: round(v, 3) for k, v in prep.marks.items()}
    result["step_ms"] = {
        k: float(np.percentile(step_ms, q))
        for k, q in (("p10", 10), ("p50", 50), ("p90", 90), ("p99", 99),
                     ("max", 100))
    }
    result["window_s"] = window_s
    # CPU seconds of the window: of the whole process, and of the thread
    # train() runs on (the hook is called there). Where a run is slow and
    # the thread's seconds are not, the thread waited; for reading the
    # spread of a host-bound cell. No metric, and the driver ignores it
    result["window_cpu_s"] = {
        "process": hook.cpu_s[0], "train_thread": hook.cpu_s[1]}
    result["check_s"] = check_s
    if is_chip:
        # what a shorter window of this same run would have read: the
        # rate up to the first step that ends past each length. For
        # choosing ``run_seconds``; no metric, and the driver ignores it
        elapsed = stamps - stamps[0]
        result["edges_per_s_chip_by_window_s"] = {
            str(t): float(n * step_costs["edges"] / elapsed[n])
            for t in (5, 10, 20, 30, 40)
            for n in [int(np.searchsorted(elapsed, t))]
            if 0 < n < len(elapsed)
        }
    # the journalled stalls of this process (the program's own journal:
    # leaf, thread CPU time, collections), each with where in the window
    # it ended, for run.py to print on standard error
    from euler_tpu import telemetry

    result["stall_journal"] = [
        dict(e, window_s_at_end=round(e["end_us"] * 1e-6 - hook.t_open, 3))
        for e in telemetry.stall_journal()
    ]
    if calibrate:
        result["calibration"] = calibration_numbers(prep, hook)
    # compared numbers beside their limits: last on the line and on stderr
    result["compared"] = table
    return result


def calibration_numbers(prep: Prepared, hook: Hook) -> dict:
    """The control (the reference in bfloat16 in the program's place) and
    the planted faults (the reference on half of the batch, and on one
    chip's share of it) by the same numbers."""
    import jax.numpy as jnp

    b = prep.cell.global_batch
    out = {
        "control_bf16": prep.compare(hook, dtype=jnp.bfloat16),
        "fault_half_batch": prep.compare(hook, batch_rows=b // 2),
    }
    if prep.cell.chips > 1:
        out["fault_no_exchange"] = prep.compare(
            hook, batch_rows=b // prep.cell.chips)
    return out
