"""The quickest proof that the system still starts on the chip.

Drives the main path once at the PPI reference recipe (BASELINE.json
config 1: 56,944 nodes, 50-dim features, 121 labels, batch 512, fanouts
10,10, dim 256; the graph is generated from its seed, weights are
random from a seed) through the entry points a user calls:

  (a) one epoch (111 steps) of `euler_tpu.ppi_main` with device
      sampling — the chained two-hop Pallas kernel inside the step;
  (b) one epoch with the host sampler + prefetch + device-resident
      tables — the path every graph beyond HBM takes;
  (c) `serve.run_serve` on the checkpoint (a) left, EmbedClient
      requests over TCP, then drain and close.

It asserts rather than logs: finite losses that fall, the Mosaic custom
call in the lowered train step, the batch and tables placed as the mesh
says, served rows of the right shape that are finite, bit-stable and
bit-identical to the server's direct forward, served state restored
from the checkpoint. Any phase that raises ends the run non-zero.

Uses every device JAX finds: on four chips (a) trains data=4, and the
data=2 x model=2 layout (row-sharded tables) is run and checked as
well. Everything runs in THIS process, phase after phase (run_loop.main
is re-entrant) — a chip belongs to one process, so no child is started.
Needs a TPU: on any other platform it says what it found and exits 1
before building anything. The last line of stdout is the JSON result.

    python chip_smoke.py
"""

from __future__ import annotations

import json
import logging
import os
import re
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".data", "chip_smoke")  # .gitignore lists .data/
REPORT = os.path.join(REPO, "chiprun_out", "chip_smoke.json")

RECIPE = [
    "--model", "graphsage_supervised", "--fanouts", "10,10", "--dim", "256",
    "--batch_size", "512", "--num_epochs", "1", "--log_steps", "10",
    "--device_features", "true",
]
STEPS = 111  # (max_id + 1) // batch_size at the PPI preset
MOSAIC_CALL = "tpu_custom_call"  # what a compiled Pallas TPU kernel lowers to


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


class _Capture(logging.Handler):
    """Collects the run_loop's log lines: the loss windows, the compile
    line and the draw-path lines are what the phases assert on."""

    def __init__(self):
        super().__init__(level=logging.INFO)
        self.lines: list[str] = []

    def emit(self, record):
        self.lines.append(record.getMessage())


def recipe_args(data_dir: str, extra: list[str]):
    """The flags a phase runs with, parsed the way ppi_main.run does."""
    from euler_tpu import ppi_main, run_loop

    return run_loop.define_flags().parse_args(
        [*ppi_main.PPI_DEFAULTS, "--data_dir", data_dir, *RECIPE, *extra]
    )


def train_epoch(name: str, data_dir: str, extra: list[str]) -> dict:
    """One epoch through ppi_main.run -> run_loop.main -> train();
    returns what the log said, after asserting the losses."""
    import numpy as np

    from euler_tpu import ppi_main

    model_dir = os.path.join(WORK, f"ckpt_{name}")
    shutil.rmtree(model_dir, ignore_errors=True)  # never resume a smoke
    cap = _Capture()
    logger = logging.getLogger("euler_tpu")
    logger.addHandler(cap)
    t0 = time.monotonic()
    try:
        rc = ppi_main.run(
            ["--data_dir", data_dir, "--model_dir", model_dir, *RECIPE, *extra]
        )
    finally:
        logger.removeHandler(cap)
    wall = time.monotonic() - t0
    if rc != 0:
        raise RuntimeError(f"phase {name}: ppi_main.run returned {rc}")
    windows = [
        (int(m[1]), float(m[2]))
        for m in (re.match(r"step=(\d+) loss=(\S+)", ln) for ln in cap.lines)
        if m
    ]
    losses = [loss for _, loss in windows]
    if not windows or windows[-1][0] != STEPS:
        raise AssertionError(f"phase {name}: expected {STEPS} steps: {windows}")
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"phase {name}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(
            f"phase {name}: loss did not fall: first window {losses[0]}, "
            f"last {losses[-1]}"
        )
    compiled = next(
        (ln for ln in cap.lines if ln.startswith("first step dispatched")), ""
    )
    m = re.search(r"(\d+) XLA compile\(s\), (\d+) ms", compiled)
    out = {
        "steps": windows[-1][0],
        "first_loss": losses[0],
        "last_loss": losses[-1],
        "wall_s": round(wall, 1),
        # process-cumulative (the compile histogram is global), so only
        # the first phase's line reads as cold-vs-warm cache evidence
        "compiles_at_first_step": int(m[1]) if m else None,
        "compile_ms_at_first_step": int(m[2]) if m else None,
        "draw_paths": [ln for ln in cap.lines if ln.startswith("draw path")],
        "model_dir": model_dir,
    }
    say(
        f"phase {name}: {out['steps']} steps, loss {losses[0]:.4f} -> "
        f"{losses[-1]:.4f}, {wall:.1f}s; {compiled}"
    )
    return out


def check_train_step(data_dir: str, extra: list[str]) -> dict:
    """Lower the train step of the model run_loop.build_model returns,
    placed the way train() places it, and check what the program holds
    and where the data lives: the Mosaic custom call (per shard under
    shard_map on a mesh), the batch split over 'data', the feature and
    label tables split over 'model', params replicated. Then run the
    compiled step once so the placement checked is one that executes."""
    import jax
    import numpy as np

    from euler_tpu import run_loop
    from euler_tpu import train as train_lib
    from euler_tpu.graph import device as device_graph
    from euler_tpu.parallel import (
        batch_sharding,
        make_mesh,
        pad_tables_for_mesh,
        put_global,
        replicated_sharding,
        shard_batch,
        state_sharding,
    )

    args = recipe_args(data_dir, extra)
    graph, _ = run_loop.build_graph(args)
    mesh = make_mesh(args.num_devices, model_parallel=args.model_parallel)
    n_data, n_model = mesh.shape["data"], mesh.shape["model"]
    with device_graph.kernel_mesh_scope(mesh):
        model = run_loop.build_model(args, graph)
        opt = train_lib.get_optimizer(args.optimizer, args.learning_rate)
        roots = np.asarray(
            graph.sample_node(args.batch_size, args.train_node_type)
        )
        state = model.init_state(
            jax.random.PRNGKey(args.seed), graph, roots, opt
        )
        state = pad_tables_for_mesh(state, mesh)
        shardings = state_sharding(mesh, state)
        state = put_global(state, shardings)
        batch = shard_batch(model.sample(graph, roots), mesh)
        rep = replicated_sharding(mesh)
        step = jax.jit(
            model.make_train_step(opt),
            in_shardings=(shardings, batch_sharding(mesh)),
            out_shardings=(shardings, rep, rep),
            donate_argnums=(0,),
        )
        lowered = step.lower(state, batch)
        text = lowered.as_text()
        if MOSAIC_CALL not in text:
            raise AssertionError(
                f"no {MOSAIC_CALL} in the lowered train step: the draw "
                "kernels are not in the program"
            )
        if mesh.size > 1 and "shard_map" not in text and "manual" not in text:
            raise AssertionError(
                "kernel is in the program but not under shard_map on a "
                f"{mesh.size}-device mesh"
            )

        def rows_per_device(x):
            return sorted(
                (s.device.id, s.data.shape[0]) for s in x.addressable_shards
            )

        per_dev = rows_per_device(batch["roots"])
        want = args.batch_size // n_data
        if len(per_dev) != mesh.size or {r for _, r in per_dev} != {want}:
            raise AssertionError(
                f"roots not split {want} per device over data={n_data}: "
                f"{per_dev}"
            )
        for key in ("features", "labels"):
            table = state["consts"][key]
            rows = table.shape[0] // n_model
            got = rows_per_device(table)
            if len(got) != mesh.size or {r for _, r in got} != {rows}:
                raise AssertionError(
                    f"consts[{key!r}] {table.shape} not row-split over "
                    f"model={n_model}: {got}"
                )
            # one distinct row block per 'model' index, none whole on a
            # device (unless the axis is 1 wide)
            blocks = {
                (s.index[0].start or 0) for s in table.addressable_shards
            }
            if len(blocks) != n_model:
                raise AssertionError(
                    f"consts[{key!r}]: {len(blocks)} distinct row blocks "
                    f"for model={n_model}"
                )
        for leaf in jax.tree.leaves(state["params"]):
            if not leaf.sharding.is_fully_replicated:
                raise AssertionError("params are not replicated")
        table_rows = {
            k: state["consts"][k].shape[0] // n_model
            for k in ("features", "labels")
        }
        new_state, loss, _ = lowered.compile()(state, batch)  # donates state
        if not np.isfinite(float(loss)):
            raise AssertionError(f"compiled step gave loss {loss}")
        if new_state["consts"]["features"].sharding != shardings["consts"][
            "features"
        ]:
            raise AssertionError("feature table left its sharding in a step")
    out = {
        "mesh": {"data": n_data, "model": n_model},
        "mosaic_custom_calls": text.count(MOSAIC_CALL),
        "roots_rows_per_device": want,
        "table_rows_per_device": table_rows,
    }
    say(
        f"train step on data={n_data} x model={n_model}: Mosaic custom call "
        f"IS in the lowered program ({out['mosaic_custom_calls']}x "
        f"{MOSAIC_CALL}); {want} roots per device; tables "
        f"{out['table_rows_per_device']} rows per device; params replicated"
    )
    return out


def serve_requests(data_dir: str, model_dir: str) -> dict:
    """run_serve(block=False) on a trained checkpoint, a few dozen
    EmbedClient requests over TCP, drain, close."""
    import jax
    import numpy as np

    from euler_tpu import EmbedClient, run_loop, serve
    from euler_tpu import train as train_lib
    from euler_tpu.checkpoint import Checkpointer
    from euler_tpu.parallel import make_mesh

    args = recipe_args(
        data_dir, ["--model_dir", model_dir, "--device_sampling", "false",
                   "--serve_port", "0"],
    )
    args.mode = "evaluate"  # inference sampling config, as serve.main does
    graph, _ = run_loop.build_graph(args)
    mesh = make_mesh(args.num_devices, model_parallel=args.model_parallel)
    model = run_loop.build_model(args, graph)
    ckpt = Checkpointer(model_dir)
    try:
        saved_step = ckpt.latest_step()
    finally:
        ckpt.close()
    if saved_step != STEPS:
        raise AssertionError(f"checkpoint at step {saved_step}, not {STEPS}")
    server, frontend = serve.run_serve(model, graph, args, mesh, block=False)
    try:
        # restored, not fresh: the same seed's initial params must differ
        fresh = model.init_state(
            jax.random.PRNGKey(args.seed), graph,
            np.asarray(graph.sample_node(args.batch_size, 0)),
            train_lib.get_optimizer(args.optimizer, args.learning_rate),
        )["params"]
        moved = [
            not np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(fresh),
                            jax.tree.leaves(server._state["params"]))
        ]
        if not all(moved):
            raise AssertionError(
                "served params equal a fresh init: the state did not come "
                "from the checkpoint"
            )
        rng = np.random.default_rng(0)
        repeated = 4242
        client = EmbedClient(frontend.address)
        try:
            first = client.embed([repeated])
            requests = rows = 1
            for _ in range(36):
                ids = rng.integers(0, 56944, size=int(rng.integers(1, 9)))
                ids = np.append(ids, repeated)
                emb = client.embed(ids)
                requests += 1
                rows += len(ids)
                if emb.shape != (len(ids), 256) or emb.dtype != np.float32:
                    raise AssertionError(f"served {emb.shape} {emb.dtype}")
                if not np.isfinite(emb).all():
                    raise AssertionError("non-finite served embedding")
                if emb[-1].tobytes() != first[0].tobytes():
                    raise AssertionError(
                        f"id {repeated} changed between requests"
                    )
            # the repo's own reference: the un-batched direct forward
            for nid in (repeated, int(ids[0])):
                direct = server.embed_direct(nid)
                if direct.tobytes() != client.embed([nid])[0].tobytes():
                    raise AssertionError(
                        f"served id {nid} differs from embed_direct"
                    )
            requests += 2
            rows += 2
        finally:
            client.close()
    finally:
        frontend.drain()
        server.close()
        frontend.stop()
    out = {"requests": requests, "rows": rows, "checkpoint_step": saved_step}
    say(
        f"phase serve: {requests} requests ({rows} rows) over TCP from the "
        f"step-{saved_step} checkpoint: [n, 256] float32, finite, id "
        f"{repeated} bit-identical across requests and to embed_direct"
    )
    return out


def main() -> int:
    import jax

    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    say(
        f"platform={device['platform']} device_kind={device['kind']} "
        f"count={device['count']} jax={jax.__version__}"
    )
    if device["platform"] != "tpu":
        print(
            f"chip_smoke: needs a TPU; JAX found platform="
            f"{device['platform']} ({device['kind']})", file=sys.stderr,
        )
        return 1
    for var in ("EULER_TPU_PALLAS_SAMPLING", "EULER_TPU_PALLAS_INTERPRET"):
        if os.environ.get(var):
            print(f"chip_smoke: unset {var} first", file=sys.stderr)
            return 1

    from euler_tpu.datasets import build_ppi
    from euler_tpu.parallel import enable_compile_cache

    say(f"compile cache at {enable_compile_cache()}")
    data_dir = build_ppi(os.path.join(WORK, "ppi"))
    report = {"device": device}

    on_device = ["--device_sampling", "true"]
    report["device_sampling"] = train_epoch("device", data_dir, on_device)
    chained = [
        ln for ln in report["device_sampling"]["draw_paths"]
        if "chained two-hop Pallas kernel" in ln
    ]
    if not chained:
        raise AssertionError(
            "the device-sampling epoch did not take the chained kernel: "
            f"{report['device_sampling']['draw_paths']}"
        )
    say(chained[0])
    report["train_step"] = check_train_step(data_dir, on_device)
    report["host_sampling"] = train_epoch(
        "host", data_dir, ["--device_sampling", "false"]
    )
    report["serve"] = serve_requests(
        data_dir, report["device_sampling"]["model_dir"]
    )
    if device["count"] >= 4 and device["count"] % 2 == 0:
        # the other half of multi-chip: tables row-sharded over 'model'
        mp = [*on_device, "--model_parallel", "2"]
        report["device_sampling_mp2"] = train_epoch("device_mp2", data_dir, mp)
        report["train_step_mp2"] = check_train_step(data_dir, mp)

    os.makedirs(os.path.dirname(REPORT), exist_ok=True)
    with open(REPORT, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
