"""Host->device prefetch pipeline.

The TPU replacement for the reference's AsyncOpKernel machinery
(reference tf_euler/kernels/*.cc ComputeAsync + callback chains): instead of
async graph ops inside the step graph, the sampler runs in background
threads (the native engine releases the GIL) producing batch k+1..k+depth
while the device computes step k.

Instrumented for the step-phase profiler (OBSERVABILITY.md "Step
phases"): with ``profile`` on (default: whenever telemetry is enabled)
the pipeline records

  * ``input_stall`` — consumer wall time blocked on the queue per step
    (ROADMAP item 1's acceptance metric is this histogram's mean,
    ``input_stall_ms``);
  * ``sample`` — per-worker ``make_batch`` produce time (suppress with
    ``record_sample=False`` when the caller times finer-grained phases
    inside make_batch itself, as train.py does);
  * queue-depth and workers-busy value histograms at every dequeue —
    what tells a starved queue (depth 0, workers busy) apart from
    slow/dead workers (depth 0, workers idle);
  * the ``prefetch_produced`` / ``prefetch_dropped`` /
    ``prefetch_worker_errors`` counters. A worker that dies after init
    still surfaces as the consumer's exception at its step, but the
    counter and a journaled error span make it visible in any metrics
    scrape even when the consumer is mid-step.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterator


def _now_us() -> int:
    """CLOCK_MONOTONIC µs: the clock of every span's length and place
    (trace.now_us, without importing the telemetry stack)."""
    return time.monotonic_ns() // 1000


def _profiler():
    """(record_phase, record_gauges, counter_add) when the telemetry
    stack is importable and enabled, else None — prefetch() stays
    usable in processes that never touch the native library."""
    try:
        from euler_tpu.graph.native import counter_add
        from euler_tpu.telemetry import (
            record_phase,
            record_prefetch_gauges,
            telemetry_enabled,
        )

        if not telemetry_enabled():
            return None
        return record_phase, record_prefetch_gauges, counter_add
    except Exception:
        return None


def prefetch(
    make_batch: Callable[[int], dict],
    num_steps: int,
    depth: int = 2,
    num_threads: int = 2,
    start: int = 0,
    worker_init: Callable[[int], None] | None = None,
    profile: bool | None = None,
    record_sample: bool = True,
    stall_out: list | None = None,
    step_of: Callable[[int], int] | None = None,
) -> Iterator[dict]:
    """Yield num_steps batches for steps start..start+num_steps, produced
    ahead of time by worker threads.

    make_batch(step) must be thread-safe (the graph engine is: the store is
    immutable and RNG is thread-local). worker_init(worker_idx) runs once
    at the start of each worker thread — e.g. to seed its thread-local
    sampler RNG for reproducible runs.

    profile=None enables step-phase recording iff telemetry is enabled
    (the `telemetry=0` kill-switch reaches here too); False forces the
    zero-instrumentation path. While recording, the consumer writes the
    CLOCK_MONOTONIC µs at which each step's queue wait began and ended
    into ``stall_out[0:2]`` (train() places its ``input_other`` leaf on
    both sides of that wait).

    ``step_of(item)`` is the step the spans of item ``item`` (0-based)
    are labelled with, where items are not steps (train()'s chunks of
    steps); default ``item + start``.
    """
    prof = _profiler() if profile in (None, True) else None
    label = step_of or (lambda item: item + start)
    if start:
        base_make = make_batch
        make_batch = lambda step: base_make(step + start)  # noqa: E731
    if num_threads <= 1 or depth <= 0:
        # Synchronous path: the consumer IS the producer, so every
        # sample is, by definition, a full consumer stall — exactly the
        # input_stall the async pipeline above exists to hide.
        if worker_init is not None:
            worker_init(0)
        for step in range(num_steps):
            t0 = _now_us() if prof is not None else 0
            batch = make_batch(step)
            if prof is not None:
                t1 = _now_us()
                record, gauges, count = prof
                if record_sample:
                    record("sample", t1 - t0, step=label(step), end_us=t1)
                record("input_stall", t1 - t0, step=label(step),
                       end_us=t1)
                if stall_out is not None:
                    stall_out[0], stall_out[1] = t0, t1
                gauges(0, 0)
                count("prefetch_produced")
            yield batch
        return

    out: "queue.Queue" = queue.Queue()
    cv = threading.Condition()
    next_step = [0]  # next step a worker may claim
    consumed = [0]  # steps the consumer has yielded
    busy = [0]  # workers currently inside make_batch
    stop = threading.Event()

    def worker(widx: int):
        try:
            if worker_init is not None:
                worker_init(widx)
        except Exception as e:  # surface init errors instead of hanging
            if prof is not None:
                prof[2]("prefetch_worker_errors")
            with cv:
                # claim the next unclaimed step so the consumer is
                # guaranteed to reach this error entry
                step = next_step[0]
                next_step[0] = step + 1
            out.put((step, e))
            return
        while not stop.is_set():
            with cv:
                # Backpressure: never run more than `depth` steps ahead of
                # the consumer, even across the reorder buffer — otherwise a
                # slow step would let the other workers produce (and retain)
                # arbitrarily many batches.
                while (
                    not stop.is_set()
                    and next_step[0] < num_steps
                    and next_step[0] - consumed[0] >= depth + 1
                ):
                    cv.wait(timeout=0.1)
                step = next_step[0]
                if stop.is_set() or step >= num_steps:
                    return
                next_step[0] = step + 1
                busy[0] += 1
            t0 = _now_us()
            try:
                batch = make_batch(step)
            except Exception as e:  # surface errors to the consumer
                if prof is not None:
                    # the counter + an error span make the death visible
                    # in a scrape even while the consumer is mid-step
                    prof[2]("prefetch_worker_errors")
                    try:
                        from euler_tpu.telemetry import record_span

                        record_span(_now_us() - t0, outcome=1)
                    except Exception:
                        pass
                with cv:
                    busy[0] -= 1
                out.put((step, e))
                return
            if prof is not None:
                if record_sample:
                    t1 = _now_us()
                    prof[0]("sample", t1 - t0, step=label(step),
                            end_us=t1)
                prof[2]("prefetch_produced")
            with cv:
                busy[0] -= 1
            out.put((step, batch))

    threads = [
        threading.Thread(target=worker, args=(w,), daemon=True)
        for w in range(num_threads)
    ]
    for t in threads:
        t.start()
    # Reorder: batches may complete out of order with >1 worker. The
    # pending dict is bounded by depth+1 thanks to the backpressure.
    pending: dict[int, object] = {}
    try:
        for want in range(num_steps):
            t_wait = _now_us() if prof is not None else 0
            while want not in pending:
                step, item = out.get()
                pending[step] = item
            if prof is not None:
                record, gauges, _ = prof
                t_got = _now_us()
                record("input_stall", t_got - t_wait, step=label(want),
                       end_us=t_got)
                if stall_out is not None:
                    stall_out[0], stall_out[1] = t_wait, t_got
                # ready batches beyond the one about to be consumed
                gauges(out.qsize() + len(pending) - 1, busy[0])
            item = pending.pop(want)
            if isinstance(item, Exception):
                raise item
            yield item
            with cv:
                consumed[0] = want + 1
                cv.notify_all()
    finally:
        stop.set()
        with cv:
            cv.notify_all()
        for t in threads:
            t.join(timeout=1.0)
        if prof is not None:
            # batches produced but never consumed (early close / error
            # teardown): the pipeline-efficiency side of the ledger
            dropped = sum(
                1 for v in pending.values()
                if not isinstance(v, Exception)
            )
            while True:
                try:
                    _, item = out.get_nowait()
                except queue.Empty:
                    break
                if not isinstance(item, Exception):
                    dropped += 1
            if dropped:
                prof[2]("prefetch_dropped", dropped)


def pipeline(
    start_fn: Callable[[int], object],
    finish_fn: Callable[[int, object], dict],
    num_steps: int,
    depth: int = 2,
    start: int = 0,
    worker_init: Callable[[int], None] | None = None,
    profile: bool | None = None,
    record_sample: bool = True,
    stall_out: list | None = None,
) -> Iterator[dict]:
    """Depth-N in-flight step ring over a SPLIT sampler (train.py
    ``sampler_depth=``): yield num_steps batches for steps
    start..start+num_steps, kept ``depth`` submits ahead of consumption.

    Where :func:`prefetch` overlaps steps by running whole ``make_batch``
    calls on Python worker threads, this overlaps them at the native
    layer: ``start_fn(step)`` SUBMITS the step's sampling without
    blocking (remote graphs: one eg_remote_sample_async op whose hop
    chain runs on the client's dispatcher pool — no Python thread holds
    the step open) and returns a pending token; ``finish_fn(step,
    pending)`` blocks on that token and assembles the batch. One driver
    thread keeps up to ``depth`` steps submitted, finishes them strictly
    in order, and lands results in the same bounded queue / phase
    instrumentation contract as prefetch — the consumer loop, the
    ``input_stall`` histogram, the ``eg_prefetch_*`` gauges (queue depth
    + in-flight submits), and the produced/dropped/worker-error counters
    all read identically, so train()'s consumer side is unchanged
    (``stall_out`` as in prefetch).

    Exceptions from either fn surface at the consumer's matching step,
    like prefetch; pending tokens submitted after a failure are dropped
    (their native slots recycle via the handle finalizer).
    """
    from collections import deque

    prof = _profiler() if profile in (None, True) else None
    depth = max(1, int(depth))
    if start:
        base_start, base_finish = start_fn, finish_fn
        start_fn = lambda step: base_start(step + start)  # noqa: E731
        finish_fn = (  # noqa: E731
            lambda step, pending: base_finish(step + start, pending)
        )
    # bounded: in-flight native submits are capped by the ring, finished
    # batches by the queue — the driver blocks on put when the consumer
    # falls behind, so at most depth submitted + depth+1 finished exist
    out: "queue.Queue" = queue.Queue(maxsize=depth + 1)
    stop = threading.Event()
    busy = [0]  # steps currently submitted but not yet finished

    def put(step, item) -> bool:
        while not stop.is_set():
            try:
                out.put((step, item), timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def driver():
        try:
            if worker_init is not None:
                worker_init(0)
        except Exception as e:
            if prof is not None:
                prof[2]("prefetch_worker_errors")
            put(0, e)
            return
        inflight: "deque[tuple[int, object]]" = deque()
        step = 0
        cur = 0
        try:
            while not stop.is_set() and (inflight or step < num_steps):
                while step < num_steps and len(inflight) < depth:
                    inflight.append((step, start_fn(step)))
                    step += 1
                    busy[0] = len(inflight)
                cur, pending = inflight.popleft()
                t0 = _now_us()
                batch = finish_fn(cur, pending)
                busy[0] = len(inflight)
                if prof is not None:
                    if record_sample:
                        t1 = _now_us()
                        prof[0]("sample", t1 - t0, step=cur + start,
                                end_us=t1)
                    prof[2]("prefetch_produced")
                if not put(cur, batch):
                    return
        except Exception as e:
            if prof is not None:
                prof[2]("prefetch_worker_errors")
                try:
                    from euler_tpu.telemetry import record_span

                    record_span(0, outcome=1)
                except Exception:
                    pass
            # tokens still in the ring are abandoned; their handles'
            # finalizers recycle the native slots
            put(cur if cur >= 0 else 0, e)

    t = threading.Thread(target=driver, daemon=True)
    t.start()
    try:
        for want in range(num_steps):
            t_wait = _now_us() if prof is not None else 0
            _, item = out.get()  # driver produces strictly in order
            if prof is not None:
                record, gauges, _ = prof
                t_got = _now_us()
                record("input_stall", t_got - t_wait, step=want + start,
                       end_us=t_got)
                if stall_out is not None:
                    stall_out[0], stall_out[1] = t_wait, t_got
                gauges(out.qsize(), busy[0])
            if isinstance(item, Exception):
                raise item
            yield item
    finally:
        stop.set()
        t.join(timeout=1.0)
        if prof is not None:
            dropped = 0
            while True:
                try:
                    _, item = out.get_nowait()
                except queue.Empty:
                    break
                if not isinstance(item, Exception):
                    dropped += 1
            if dropped:
                prof[2]("prefetch_dropped", dropped)
