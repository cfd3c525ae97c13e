"""Training / evaluation / embedding-export driver.

Reference equivalent: tf_euler/python/run_loop.py (run_train :95-140,
run_evaluate :143-171, run_save_embedding :174-219) — rebuilt for JAX:
MonitoredTrainingSession becomes an explicit loop over a jitted train step;
PS placement becomes mesh sharding (see parallel/mesh.py); the input
pipeline is the host sampler behind a prefetch queue.
"""

from __future__ import annotations

import contextlib
import functools
import inspect
import logging
import os
import threading
import time
from typing import Callable, Optional

import jax
import numpy as np
import optax

from euler_tpu.graph import device as device_graph
from euler_tpu.nn import metrics as metrics_lib
from euler_tpu.parallel import (
    batch_sharding,
    compiles_keep_layouts,
    make_mesh,
    pad_tables_for_mesh,
    pipeline,
    prefetch,
    put_global,
    replicated_sharding,
    shard_batch,
    state_sharding,
)

log = logging.getLogger("euler_tpu")

# The most steps train() runs in one dispatch where the model draws its
# batch on the device: enough that the host's ~1 ms a dispatch hides
# under the device's steps (a 0.6 ms GraphSAGE step on a v5e).
CHUNK_STEPS = 10
# Under a step_hook the first steps are a dispatch each: the benchmark's
# hook compares each of them with its reference.
HOOK_SINGLE_STEPS = 3

OPTIMIZERS = {
    "sgd": optax.sgd,
    "momentum": lambda lr: optax.sgd(lr, momentum=0.9),
    "adagrad": optax.adagrad,
    "adam": optax.adam,
}


def get_optimizer(name: str, lr: float):
    """Reference tf_euler/python/optimizers.py registry."""
    return OPTIMIZERS[name](lr)


def _metric_value(name: str, acc) -> float:
    if name == "f1":
        return metrics_lib.f1_from_counts(acc)
    if name == "auc":
        return metrics_lib.auc_from_counts(acc)
    return float(acc[0] / max(acc[1], 1))  # running mean


def _metric_accumulate(name: str, acc, value):
    value = np.asarray(value)
    if name in ("f1", "auc"):
        return acc + value
    return np.array([acc[0] + float(value), acc[1] + 1.0])


def _metric_zero(name: str):
    if name == "f1":
        return np.zeros(3)
    if name == "auc":
        return np.zeros((2, metrics_lib.AUC_BINS))
    return np.zeros(2)


@contextlib.contextmanager
def _cache_keyed_with_metadata():
    """Compile under a persistent-cache key that includes the program's
    metadata. The usual key strips it, so a hit may hand back an
    executable another commit compiled: the same code under that commit's
    ``op_name`` paths and, since XLA names instructions after them, that
    commit's instruction names. A profiled run has to execute, and read
    the text of, a program that carries its own scopes."""
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        yield
    finally:
        jax.config.update(flag, before)


def write_step_hlo(step_fn, args, profile_dir: str) -> None:
    """Leave the compiled train step's HLO text in
    ``<profile_dir>/trace.STEP_HLO_FILE``: every instruction with the
    ``op_name`` it was traced under, which is how a reader of the capture
    gets from a device op (the capture names it by its instruction) to
    its ``trace.STEP_SCOPES`` scope. A compile-cache hit: the profiled
    run's first step compiled this program under the same key. The same
    compiled step's ``memory_analysis()`` sets the temporaries gauge and
    one route-log line (an unprofiled run lowers and compiles nothing
    for either)."""
    from euler_tpu import devprof
    from euler_tpu.trace import STEP_HLO_FILE

    with _cache_keyed_with_metadata():
        compiled = step_fn.lower(*args).compile()
    os.makedirs(profile_dir, exist_ok=True)
    with open(os.path.join(profile_dir, STEP_HLO_FILE), "w") as f:
        f.write(compiled.as_text())
    # what the step needs beside its arguments and results: the gauge
    # `step_temp_bytes` (memory_stats() does not count it)
    sizes = devprof.record_step_memory(compiled)
    if sizes:
        log.info(
            "train step memory: temporaries %.3f GB, arguments %.3f GB "
            "(%.3f GB of them aliased to results), results %.3f GB",
            *(sizes[k] / 1e9
              for k in ("temp", "argument", "alias", "output")),
        )


def _chunk_program(train_step, mesh):
    """``chunk(state, batches, n) -> (state, loss, metrics, batch)``:
    ``train_step`` over the first ``n`` of the CHUNK_STEPS batches
    stacked along ``batches``' leading axis, in one program. ``loss`` is
    the last step's, ``metrics`` the steps' metrics in CHUNK_STEPS rows
    (rows from ``n`` on hold zeros) and ``batch`` the last step's batch.
    Step k of a chunk computes what a dispatch of step k alone does: the
    same batch, sharded as one, into the same step. The step is traced
    once: a jit of its own, whose trace the result shapes and the loop
    body share, so a chunk of one step and a chunk of ten are one
    program."""
    import jax.numpy as jnp

    step = jax.jit(train_step)
    one = batch_sharding(mesh)

    def batch_at(batches, i):
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                jax.lax.dynamic_index_in_dim(x, i, keepdims=False), one),
            batches)

    def chunk(state, batches, n):
        # (of the loop body's own types: the shapes' trace is its trace)
        _, loss, metric = jax.eval_shape(step, state, batch_at(batches, 0))

        def body(i, carry):
            state, _, metrics = carry
            state, loss, metric = step(state, batch_at(batches, i))
            metrics = jax.tree.map(
                lambda rows, m: rows.at[i].set(m), metrics, metric)
            return state, loss, metrics

        state, loss, metrics = jax.lax.fori_loop(0, n, body, (
            state, jnp.zeros(loss.shape, loss.dtype),
            jax.tree.map(
                lambda m: jnp.zeros((CHUNK_STEPS, *m.shape), m.dtype),
                metric)))
        return state, loss, metrics, batch_at(batches, n - 1)

    # the program, its HLO module and its compile events keep the step's
    # name
    chunk.__name__ = chunk.__qualname__ = getattr(
        train_step, "__name__", "train_step")
    return chunk


def _kernel_mesh_scoped(fn):
    """Run ``fn`` with its ``mesh`` argument (default: every device)
    registered for per-shard Pallas draws (device.kernel_mesh_scope),
    so a device-sampling model keeps the kernel on a multi-chip mesh
    however the trainer is reached — run_loop, the examples, a direct
    call."""
    sig = inspect.signature(fn)

    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        bound = sig.bind(*args, **kwargs)
        if bound.arguments.get("mesh") is None:
            bound.arguments["mesh"] = make_mesh()
        with device_graph.kernel_mesh_scope(bound.arguments["mesh"]):
            return fn(*bound.args, **bound.kwargs)

    return scoped


@_kernel_mesh_scoped
def train(
    model,
    graph,
    source_fn: Callable[[int], np.ndarray],
    num_steps: int,
    optimizer: str = "adam",
    learning_rate: float = 0.01,
    mesh=None,
    log_every: int = 100,
    seed: int = 42,
    prefetch_depth: int = 2,
    prefetch_threads: int = 2,
    sampler_depth: int = 2,
    state: Optional[dict] = None,
    log_fn=None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 0,
    profile_dir: Optional[str] = None,
    profile_steps: tuple = (10, 20),
    step_hook=None,
    phase_profile: Optional[bool] = None,
):
    """Train and return (state, history).

    Where the model draws its batch on the device (``model.device_sampling``
    and no remote pipeline), up to CHUNK_STEPS steps go up in one jitted
    call (``_chunk_program``: one program, its step count an argument).
    A dispatch ends early where the loop acts between two steps: a
    hook's first HOOK_SINGLE_STEPS steps, the profiler's start and stop,
    a log window's end, a checkpoint, the last step. Everywhere else a
    dispatch is one step.

    step_hook(step) runs on the training thread after every dispatch,
    once for each of its steps (run_loop's --metrics_every JSONL emitter
    rides here; the hook gates itself, so the per-step cost is one call
    + one modulo). A hook that takes the keywords is fed what the
    dispatch's last step produced, once a dispatch, step_hook(step,
    state=, batch=, loss=) (looked up once, before the loop: the
    benchmark's hook reads its first steps there); a hook that returns
    True ends the loop after that dispatch.

    phase_profile records the step-phase histograms (OBSERVABILITY.md
    "Step phases"): input_stall + sample inside the prefetch pipeline,
    h2d (host->device transfer), device (the jitted call plus the fence
    of the dispatches that have one: host wall, not device time — the
    device's share of a step is read from a capture's scopes), host
    (optimizer/bookkeeping tail), and the whole-iteration wall. On this
    thread the leaves input_stall, input_other, h2d, dispatch, hook,
    fence, log_flush, checkpoint and host_other tile every iteration
    (one dispatch) on one clock (device = dispatch + fence and host =
    the other four are kept as histograms), ``dispatch_steps`` counts
    each dispatch's steps, and a StallJournal journals the iterations
    that took several times the running median. Every leaf is a clock
    reading, so recording changes nothing about when the loop
    synchronises. None (default) follows the telemetry kill-switch:
    profiling on when telemetry is on, none of it with `telemetry=0`.

    The thread waits for the device once every 32 steps (`fence`, after
    the dispatch that passes a multiple, for the dispatch before it:
    bounds what is queued; every dispatch, for itself, on a virtual CPU
    mesh) and once a log window (`log_flush`: one device-to-host pull of
    the window's metrics and its last loss, after the next dispatch),
    with phase_profile on or off: the chip has the last dispatch's work
    queued while the thread waits.

    source_fn(step) -> int64 root-node batch (fixed size, divisible by the
    mesh size). All sampling runs in the prefetch workers.

    sampler_depth enables the native async pipeline on REMOTE graphs:
    instead of prefetch worker threads each blocking inside a full
    model.sample(), one driver thread keeps up to sampler_depth steps
    submitted through the engine's completion queue
    (model.sample_start -> eg_remote_sample_async; the hop chain runs as
    continuations on the client dispatcher pool) and finishes them in
    order (model.sample_finish). Step k+1..k+sampler_depth sampling
    overlaps step k's H2D + device compute with zero dedicated sampling
    threads, which is what drives input_stall_ms to ~0 (ROADMAP item 1,
    PERF.md "Pipelined sampling"). sampler_depth=0 disables the split
    and always uses the thread-pool prefetch; local in-process graphs
    ignore it (no wire to overlap — they stay on prefetch).

    Multi-process (jax.distributed initialized, process_count > 1):
    source_fn yields this process's LOCAL batch (global batch /
    process_count roots); each process samples its own subgraphs and the
    batches concatenate across processes onto the global mesh
    (shard_batch), with XLA all-reducing gradients across process
    boundaries inside the jitted step. State is initialised identically
    everywhere (same seed) and placed via put_global. checkpoint_dir
    must then be a path every process can reach (orbax coordinates the
    distributed save) or None.

    checkpoint_dir enables MonitoredTrainingSession-style periodic save +
    resume-from-latest (reference run_loop.py:132-138); profile_dir captures
    a JAX profiler trace over profile_steps (the reference's ProfilerHook,
    run_loop.py:124-126), and leaves the compiled step's HLO text beside
    it (trace.STEP_HLO_FILE: the map from a device op of the capture to
    the named scope it was traced under). The host->device copies of
    the first ~prefetch_depth profiled steps were issued by the prefetch
    workers before the trace starts and won't appear in it.
    """
    n_mesh_devices = int(np.prod(mesh.devices.shape))
    cpu_virtual_mesh = (
        n_mesh_devices > 1
        and mesh.devices.reshape(-1)[0].platform == "cpu"
    )
    # Async dispatch depth must be 1 on a multi-device CPU (virtual)
    # mesh: XLA-CPU collectives BLOCK a shared pool thread inside the
    # all-reduce rendezvous, so device programs queued from later
    # steps can consume every pool thread while an earlier step's
    # rendezvous still waits for its last participant — a livelock
    # XLA resolves by aborting the process after 40 s. Real TPU
    # queues per-device streams in hardware; a modest sync there just
    # bounds queued-buffer memory.
    sync_every = 1 if cpu_virtual_mesh else 32
    opt = get_optimizer(optimizer, learning_rate)
    from euler_tpu import devprof, telemetry

    if phase_profile is None:
        try:
            phase_profile = telemetry.telemetry_enabled()
        except Exception:
            phase_profile = False
    # Set-up, up to the loop: what of it no span inside claims (the
    # tables' export, slabs and upload where init_state builds them, the
    # listener's trace / lower / compile) is this span's own time.
    with telemetry.setup_span("setup_state_place", on=phase_profile):
        if state is None:
            state = model.init_state(
                jax.random.PRNGKey(seed), graph, source_fn(0), opt
            )
        rep = replicated_sharding(mesh)
        # Params/opt replicated; per-node tables row-sharded over the mesh's
        # 'model' axis when present (pure DP: everything replicated); the
        # Scalable* stores also carry their pinned rows-major device layout
        # from here through the step's input and output. The state is the
        # step's to donate, so a store that had to be re-laid is not kept.
        state = pad_tables_for_mesh(state, mesh)
        shardings = state_sharding(mesh, state)
        state = put_global(state, shardings, consume=True)
        model.describe_state(state)

        ckpt = None
        start_step = 0
        if checkpoint_dir:
            from euler_tpu.checkpoint import Checkpointer

            ckpt = Checkpointer(checkpoint_dir)
            latest = ckpt.latest_step()
            if latest is not None:
                state = ckpt.restore(state, latest)
                state = put_global(state, shardings, consume=True)
                start_step = latest
                (log_fn or log.info)(
                    f"resumed from {checkpoint_dir} at step {latest}"
                )
            if checkpoint_every <= 0:
                checkpoint_every = max(num_steps // 10, 1)
    # remote graphs: the native async pipeline (start_batch, below)
    use_pipeline = (
        sampler_depth > 0 and getattr(graph, "mode", None) == "remote"
    )
    # A model that draws its fan-out on the device takes only roots and a
    # seed from the host a step: its steps go up CHUNK_STEPS at a time,
    # one dispatch each. Every other model samples on the host, a step a
    # dispatch.
    chunked = not use_pipeline and bool(getattr(model, "device_sampling",
                                                False))
    hook_is_fed = step_hook is not None and _takes_keywords(
        step_hook, ("state", "batch", "loss"))
    train_step = model.make_train_step(opt)
    if chunked:
        step_fn = jax.jit(
            _chunk_program(train_step, mesh),
            in_shardings=(shardings, batch_sharding(mesh, stacked=True),
                          rep),
            out_shardings=(shardings, rep, rep, batch_sharding(mesh)),
            donate_argnums=(0,),
        )
        # every step count a dispatch can have, on the mesh once
        step_counts = [shard_batch(np.int32(n), mesh)
                       for n in range(CHUNK_STEPS + 1)]
    else:
        step_fn = jax.jit(
            train_step,
            in_shardings=(shardings, batch_sharding(mesh)),
            out_shardings=(shardings, rep, rep),
            donate_argnums=(0,),
        )

    def chunk_end(done: int) -> int:
        """The step at which the dispatch that starts after ``done``
        steps ends: CHUNK_STEPS on, or sooner where the loop has to act
        between two steps (a hook's first steps, the profiler's start
        and stop, a log window's end, a checkpoint, the last step)."""
        ends = [done + CHUNK_STEPS, num_steps]
        if log_every > 0:
            ends.append(done + log_every - (done - start_step) % log_every)
        if ckpt:
            ends.append((done // checkpoint_every + 1) * checkpoint_every)
        if profile_dir:
            ends += [start_step + p for p in profile_steps
                     if start_step + p > done]
        if step_hook is not None:
            ends += range(done + 1, HOOK_SINGLE_STEPS + 1)
        return min(ends)

    # chunk j's first step by j, filled in order as the workers claim
    # them; the prefetch queue keeps the claims within its depth of one
    # another, so the entries far behind the newest are dropped
    chunk_starts = {0: start_step}
    chunk_lock = threading.Lock()

    def chunk_span(j: int) -> tuple:
        with chunk_lock:
            last = max(chunk_starts)
            while last <= j:
                chunk_starts[last + 1] = chunk_end(chunk_starts[last])
                last += 1
                chunk_starts.pop(last - 1024, None)
            return chunk_starts[j], chunk_starts[j + 1]

    stall_out = journal = None
    if phase_profile:
        from euler_tpu.telemetry import (
            StallJournal,
            record_phase,
            record_phase_hist,
            record_phase_span,
        )

        from euler_tpu.trace import now_us as clock

        # `clock` (CLOCK_MONOTONIC µs) is the one clock of every span
        # here: a span's length and its place come from the same readings
        stall_out = [0, 0]  # the consumer's queue wait, by prefetch
        journal = StallJournal()

        def leaf(name, start_us, step, leaves):
            """Close this thread's leaf ``name`` that began at
            ``start_us``: one reading is its end and the next leaf's
            start. Returns it."""
            end_us = clock()
            leaves[name] = end_us - start_us
            record_phase(name, end_us - start_us, step=step, end_us=end_us)
            return end_us
    # The prefetch workers also issue the host->device copy, so H2D of
    # batch k+1 overlaps compute of step k (up to prefetch_depth+1 staged
    # batches in device memory) — except on a virtual CPU mesh. XLA's CPU
    # multi-device backend shares one in-process communicator: device_put
    # issued from prefetch worker threads can starve a collective
    # rendezvous inside a concurrently executing step (7 of 8
    # participants arrive, then a fatal 40s termination timeout). Real
    # TPU/GPU devices transfer asynchronously and don't have this hazard;
    # on a virtual CPU mesh, transfer on the consumer thread.
    device_prefetch = not cpu_virtual_mesh

    def make_batch(item):
        """(batch, steps) of one dispatch: step ``item``'s batch, or,
        chunked, chunk ``item``'s batches stacked (None past the last
        step). With device_prefetch, device_put runs here inside the
        prefetch worker, so the host->device copy of dispatch k+1
        overlaps device compute of dispatch k (the copy releases the
        GIL)."""
        t0 = clock() if phase_profile else None
        if not chunked:
            # prefetch applies the start offset before calling: item is
            # already the absolute step index here
            return staged(model.sample(graph, source_fn(item)), item, t0), 1
        first, end = chunk_span(item)
        if first >= num_steps:
            return None
        batches = [model.sample(graph, source_fn(s))
                   for s in range(first, end)]
        # the slots past the chunk's steps are never run
        batches += batches[-1:] * (CHUNK_STEPS - len(batches))
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *batches)
        return staged(stacked, first, t0), end - first

    def staged(batch, step, t0):
        """A worker's spans of one produced batch: sample from t0, then
        (with device_prefetch) its h2d."""
        if phase_profile:
            t1 = clock()
            record_phase("sample", t1 - t0, step=step, end_us=t1)
        if device_prefetch:
            batch = shard_batch(batch, mesh, stacked=chunked)
            devprof.count_h2d(batch)
            if phase_profile:
                t2 = clock()
                record_phase("h2d", t2 - t1, step=step, end_us=t2)
        return batch

    # Native async pipeline (remote graphs only): start_batch submits the
    # step's whole fan-out into the engine's completion queue and returns
    # immediately; finish_batch blocks on the handle and assembles the
    # batch. The split rides the same phase-recording contract as
    # make_batch — "sample" here is the time spent WAITING on the handle,
    # so a fully-hidden pipeline reads as sample ~ 0 in the phase table.
    def start_batch(step):
        return model.sample_start(graph, source_fn(step))

    def finish_batch(step, pending):
        t0 = clock() if phase_profile else None
        return staged(model.sample_finish(graph, pending), step, t0), 1

    name = model.metric_name
    history = []
    t0 = time.time()
    # Metrics stay on device inside the logging window — forcing them to
    # host every step would sync the pipeline and stall the prefetch overlap
    # (JAX dispatch is async; only materialize at the log boundary). One
    # entry a dispatch: a step's metric, or a chunk's stacked buffers of
    # which its first `steps` rows were written.
    window_metrics = []
    window_steps = []
    window_start = start_step  # the step the window began after
    # A closed window is pulled after the next dispatch, and the fence
    # waits for the dispatch before the last (prev_loss): either way the
    # last one's work is still queued while the thread waits, so the
    # chip does not idle while it issues the next. (A virtual CPU mesh
    # fences the last dispatch: see sync_every.)
    closed = None
    prev_loss = None
    # a model that counts events inside its step (Model.step_counters)
    # returns (metric, counts) for its metric: the counts ride the
    # window's one pull and are added into the native ledger there
    counter_names = tuple(getattr(model, "step_counters", ()))
    if counter_names:
        from euler_tpu.graph.native import counter_add
    last_loss = None
    steps_done = start_step

    def flush(dispatched, steps, loss, end):
        """Log and keep one window: its dispatches' metrics, their steps,
        its last loss and the step it ended at."""
        nonlocal t0
        # Metric/loss materialization is the training loop's d2h point:
        # one pull of the whole window, whose copies the loop started,
        # accumulated on the host.
        devprof.count_d2h((dispatched, loss))
        metrics, loss = jax.device_get((dispatched, loss))
        if chunked:  # a step's metrics are a row of its chunk's
            metrics = [jax.tree.map(lambda x, i=i: x[i], m)
                       for m, n in zip(metrics, steps) for i in range(n)]
        if counter_names:
            metrics, counts = zip(*metrics)
            for cname, total in zip(
                    counter_names, np.sum(counts, axis=0, dtype=np.float64)):
                counter_add(cname, int(total))
        acc = _metric_zero(name)
        for m in metrics:
            acc = _metric_accumulate(name, acc, m)
        loss_v = float(loss)
        mv = _metric_value(name, acc)
        dt = time.time() - t0
        sps = sum(steps) / dt
        history.append({"loss": loss_v, name: mv, "steps_per_sec": sps})
        (log_fn or log.info)(
            f"step={end} loss={loss_v:.4f} "
            f"{name}={mv:.4f} steps/s={sps:.2f}"
        )
        t0 = time.time()

    def seed_worker(widx: int):
        # Deterministic per-worker sampler streams: the native RNG is
        # thread-local, so each prefetch worker gets its own seeded stream
        # derived from the run seed (reference samplers are unseeded).
        # Multi-process data parallelism folds the process index in —
        # with identical streams every process would draw the SAME local
        # roots, silently collapsing the global batch to one process's.
        from euler_tpu.graph.native import lib

        lib().eg_seed(
            seed * 1_000_003 + jax.process_index() * 8_191 + widx + 1
        )

    profiling = False
    trace_began = None  # the step in which the profiler was started
    if phase_profile:
        t_step = clock()
    if use_pipeline:
        batches = pipeline(
            start_batch,
            finish_batch,
            num_steps - start_step,
            depth=sampler_depth,
            start=start_step,
            worker_init=seed_worker,
            profile=phase_profile,
            record_sample=False,  # finish_batch above records sample/h2d
            stall_out=stall_out,
        )
    else:
        batches = prefetch(
            make_batch,
            # chunked: as many chunks as there can be (one step each);
            # the loop ends at the last step, before the items past it
            num_steps - start_step,
            prefetch_depth,
            prefetch_threads,
            start=0 if chunked else start_step,
            worker_init=seed_worker,
            profile=phase_profile,
            record_sample=False,  # make_batch above records sample/h2d
            stall_out=stall_out,
            step_of=(lambda j: chunk_span(j)[0]) if chunked else None,
        )
    try:
        for batch, n in batches:
            # With phase_profile the leaves tile this thread's iteration,
            # one dispatch of n steps, each ending where the next begins
            # (`mark`): input_other (since the last body's end, around
            # the queue wait that prefetch recorded as input_stall) | h2d
            # | dispatch | hook | fence (where a multiple of sync_every
            # steps was passed) | log_flush | checkpoint | host_other.
            # `step` spans body end to body end.
            cur = steps_done  # 0-based first step, matches prefetch labels
            if profile_dir and steps_done - start_step == profile_steps[0]:
                # the device lane of the capture holds the ops of the
                # profiled steps and no others: nothing is still queued
                jax.block_until_ready(last_loss)
                jax.profiler.start_trace(profile_dir)
                # Stamp the monotonic-clock marker so the device lanes of
                # this capture can be time-aligned with the host phase
                # events in the merged trace export (trace.py ingestion).
                from euler_tpu.trace import stamp_alignment

                (log_fn or log.info)(
                    "profiler clock aligned with CLOCK_MONOTONIC within "
                    f"{stamp_alignment()} us")
                profiling = True
                if phase_profile:
                    # the profiler's own start (and stop, below) lies
                    # between two steps and belongs to neither; what of
                    # its start-up still runs on under this step's fence
                    # (seconds, at times) is no stall of the loop either
                    t_step = clock()
                    trace_began = cur
            if phase_profile:
                mark = clock()
                w0, w1 = stall_out
                if w0 < t_step:  # no wait recorded since the last body
                    w0 = w1 = t_step
                leaves = {"input_stall": w1 - w0,
                          "input_other": mark - t_step - (w1 - w0)}
                record_phase_hist("input_other", leaves["input_other"])
                record_phase_span("input_other", t_step, w0, cur)
                record_phase_span("input_other", w1, mark, cur)
            if not device_prefetch:
                batch = shard_batch(batch, mesh, stacked=chunked)
                devprof.count_h2d(batch)
                if phase_profile:
                    mark = leaf("h2d", mark, cur, leaves)
            args = (state, batch, step_counts[n]) if chunked else (
                state, batch)
            if cur == start_step:  # this call compiles
                with contextlib.ExitStack() as compiling:
                    if profile_dir:
                        compiling.enter_context(_cache_keyed_with_metadata())
                    compiling.enter_context(compiles_keep_layouts(shardings))
                    out = step_fn(*args)
            else:
                out = step_fn(*args)
            if chunked:  # and the batch its last step ran on, for the hook
                state, last_loss, metric, batch = out
            else:
                state, last_loss, metric = out
            if cur == start_step and devprof.devprof_enabled():
                # Relaunch-cost visibility: what set-up was made of, and
                # the compiles (warm cache: ~0 ms on the second launch)
                (log_fn or log.info)(
                    devprof.first_step_line(step_fn.__name__))
            if phase_profile:
                mark = t_host = leaf("dispatch", mark, cur, leaves)
                record_phase_hist("dispatch_steps", n)
            # the window's values start for the host as they are produced,
            # so the flush finds all but the last dispatch's there already
            for x in jax.tree.leaves(metric):
                x.copy_to_host_async()
            window_metrics.append(metric)
            window_steps.append(n)
            steps_done += n
            window_full = steps_done - window_start == log_every
            if window_full:
                last_loss.copy_to_host_async()
            if step_hook is not None:
                if hook_is_fed:
                    hook_ends = step_hook(
                        steps_done, state=state, batch=batch, loss=last_loss)
                else:
                    # a hook of the step alone hears every step
                    for done in range(cur + 1, steps_done + 1):
                        hook_ends = step_hook(done)
                        if hook_ends is True:
                            break
                if phase_profile:
                    mark = leaf("hook", mark, cur, leaves)
                if hook_ends is True:
                    break
            if profile_dir and cur == start_step:
                # after the first step's hook, which may read the compile
                # ledger as of the first dispatch
                with compiles_keep_layouts(shardings):
                    write_step_hlo(step_fn, (state, *args[1:]), profile_dir)
            if steps_done // sync_every > cur // sync_every:
                jax.block_until_ready(
                    last_loss if cpu_virtual_mesh else prev_loss)
                if phase_profile:
                    mark = leaf("fence", mark, cur, leaves)
            prev_loss = last_loss
            if closed is not None:
                flush(*closed)
                closed = None
                if phase_profile:
                    mark = leaf("log_flush", mark, cur, leaves)
            if window_full:
                closed = (window_metrics, window_steps, last_loss, steps_done)
                window_metrics, window_steps = [], []
                window_start = steps_done
            if ckpt and steps_done % checkpoint_every == 0:
                ckpt.save(steps_done, state)
                if phase_profile:
                    mark = leaf("checkpoint", mark, cur, leaves)
            if phase_profile:
                now = leaf("host_other", mark, cur, leaves)
                # the parents, one sample a dispatch, cut from their
                # leaves' own clock readings: device = dispatch + its
                # fence, if it has one; host = the rest since the dispatch
                fence_us = leaves.get("fence", 0)
                record_phase_hist("device", leaves["dispatch"] + fence_us)
                record_phase_hist("host", now - t_host - fence_us)
                record_phase("step", now - t_step, step=cur, end_us=now)
                if cur != trace_began:
                    journal.step(cur, t_step, now, leaves)
                t_step = now
            if profiling and steps_done - start_step >= profile_steps[1]:
                jax.block_until_ready(last_loss)
                jax.profiler.stop_trace()
                profiling = False
                (log_fn or log.info)(
                    f"profiler trace written to {profile_dir}")
                if phase_profile:
                    t_step = clock()
            if steps_done >= num_steps:
                break
    finally:
        if journal is not None:
            journal.close()
    if closed is not None:
        flush(*closed)
    if window_metrics:  # final partial window
        flush(window_metrics, window_steps, last_loss, steps_done)
    if profiling:
        jax.block_until_ready(last_loss)
        jax.profiler.stop_trace()
        (log_fn or log.info)(f"profiler trace written to {profile_dir}")
    if ckpt:
        # final save only when NEW steps ran: a re-launch that resumed at
        # num_steps (nothing left to train) must not re-save the step it
        # restored — orbax raises StepAlreadyExistsError on the collision
        if steps_done > start_step and steps_done % checkpoint_every != 0:
            ckpt.save(steps_done, state, force=True)
        ckpt.close()
    return state, history


def _takes_keywords(fn, names) -> bool:
    """Whether ``fn`` can be called with every one of ``names`` as a
    keyword."""
    try:
        params = inspect.signature(fn).parameters.values()
    except (TypeError, ValueError):
        return False
    return any(p.kind is p.VAR_KEYWORD for p in params) or set(names) <= {
        p.name for p in params
        if p.kind in (p.POSITIONAL_OR_KEYWORD, p.KEYWORD_ONLY)}


@_kernel_mesh_scoped
def evaluate(
    model,
    graph,
    source_iter,
    state,
    mesh=None,
    log_fn=None,
):
    """Streaming evaluation over an iterator of root-node batches
    (reference run_loop.py:143-171).

    Multi-process: every process must iterate the SAME global batches
    (collectives run in lockstep); each samples only its contiguous
    1/process_count slice and shard_batch concatenates — the jitted
    metric is computed over the reassembled global batch, so the result
    is identical to single-process."""
    rep = replicated_sharding(mesh)
    state = pad_tables_for_mesh(state, mesh)
    shardings = state_sharding(mesh, state)
    state = put_global(state, shardings)
    eval_fn = jax.jit(
        model.make_eval_step(),
        in_shardings=(shardings, batch_sharding(mesh)),
        out_shardings=(rep, rep),
    )
    name = model.metric_name
    acc = _metric_zero(name)
    losses = []
    n_proc = jax.process_count()
    with compiles_keep_layouts(shardings):  # eval_fn compiles in here
        for ids in source_iter:
            if n_proc > 1:
                ids = np.asarray(ids)
                if len(ids) % n_proc:
                    raise ValueError(
                        f"eval batch {len(ids)} not divisible by "
                        f"{n_proc} processes"
                    )
                per = len(ids) // n_proc
                ids = ids[jax.process_index() * per:][:per]
            batch = shard_batch(model.sample(graph, ids), mesh)
            loss, metric = eval_fn(state, batch)
            acc = _metric_accumulate(name, acc, metric)
            losses.append(float(loss))
    result = {name: _metric_value(name, acc), "loss": float(np.mean(losses))}
    (log_fn or log.info)(f"eval: {result}")
    return result


@_kernel_mesh_scoped
def save_embedding(
    model,
    graph,
    max_id: int,
    state,
    batch_size: int = 1024,
    mesh=None,
):
    """Export embeddings for ids 0..max_id as a [max_id+1, dim] array
    (reference run_loop.py:174-219 exports .npy + id file).

    Multi-process: each process samples its contiguous slice of every
    chunk; the output sharding is replicated there (XLA all-gathers over
    ICI) so every process returns the full matrix — a batch-sharded
    output would span non-addressable devices and be unfetchable."""
    state = pad_tables_for_mesh(state, mesh)
    shardings = state_sharding(mesh, state)
    state = put_global(state, shardings)
    n_proc = jax.process_count()
    if batch_size % (n_proc or 1):
        raise ValueError(
            f"batch_size {batch_size} not divisible by {n_proc} processes"
        )
    embed_fn = jax.jit(
        model.make_embed_step(),
        in_shardings=(shardings, batch_sharding(mesh)),
        out_shardings=(
            replicated_sharding(mesh) if n_proc > 1
            else batch_sharding(mesh)
        ),
    )
    chunks = []
    ids = np.arange(max_id + 1, dtype=np.int64)
    pad = (-len(ids)) % batch_size
    padded = np.concatenate([ids, np.zeros(pad, dtype=np.int64)])
    per = batch_size // n_proc
    with compiles_keep_layouts(shardings):  # embed_fn compiles in here
        for i in range(0, len(padded), batch_size):
            chunk = padded[i : i + batch_size]
            if n_proc > 1:
                chunk = chunk[jax.process_index() * per:][:per]
            batch = shard_batch(model.sample_embed(graph, chunk), mesh)
            chunks.append(np.asarray(embed_fn(state, batch)))
    out = np.concatenate(chunks, axis=0)[: len(ids)]
    return out
