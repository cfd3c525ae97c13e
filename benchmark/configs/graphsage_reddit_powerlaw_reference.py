"""This configuration's plain reference and adapter: supervised GraphSAGE,
two hops, mean aggregator, float32 at ``highest`` matmul precision
(``benchmark/sage_reference.py``, whose docstring states what each
function of the protocol does), as ``graphsage_reddit_reference.py`` is.
The configuration file beside this one gives the sizes and the loss
(``sigmoid_loss``: softmax cross-entropy over 41 classes).

What this configuration judges beyond ``graphsage_reddit``: draws exact
in proportion to weight over the WHOLE neighbour list of a heavy-tailed
graph (rows up to 20,248 long, weights 1 to 8), where ``graphsage_reddit``
draws uniformly over at most 60 unit-weight slots. ``check.py`` asks the
graph function (``graphsage_reddit_powerlaw_graph.py``) pair by pair:
``are_edges`` (``draw_foreign``: every drawn id is a neighbour of its
parent) and the weight-ranked ``edge_quantiles`` (``draw_skew``: a draw in
proportion to weight reads 0.5, a uniform draw over slots about 0.35 on a
long row, a slab kept to a row's 60 heaviest edges near 1).

What the harness and ``check.py`` call here, all of it: ``init_state``,
``drawn_fanouts``, ``drawn_hops``, ``reference_batch``, ``batch_rows``,
``first_gradient``, ``compared_state``, ``train_steps``. The work a step
needs by shape is ``graphsage_alias_costs.py`` (``"costs"`` in the file).

At load it refuses a program whose ``run_loop.build_model`` does not apply
``--alias_sampling`` (``refuse_program_without_alias_form``).
"""

from benchmark.sage_reference import (  # noqa: F401
    adam_init,
    adam_update,
    batch_rows,
    compared_state,
    drawn_fanouts,
    drawn_hops,
    first_gradient,
    from_program,
    init_params,
    init_state,
    loss_fn,
    param_shapes,
    reference_batch,
    to_program,
    train_steps,
)


def refuse_program_without_alias_form() -> None:
    """End the run at once, exit code 1 and no result line, where the
    program loaded beside this file builds a model from
    ``--alias_sampling true`` that does not sample from alias tables: a
    program from before the cell, whose ``build_model`` leaves the flag to
    ``main()``. Its harness would build the padded slab ``[2090001,
    20248]`` of this graph (about 340 GB of ``nbr`` and ``cum``) and fill
    pages for minutes. The harness loads this file after ``build_graph``
    and before ``build_model``, so nothing of that is begun. Raises
    ``SystemExit`` with its message, as ``node2vec_ppi_reference.py``
    does: the harness closes what it had opened, ``run.py`` prints the
    message and leaves with code 1. The reference used alone (no program
    loaded) is held to nothing."""
    import sys

    if "euler_tpu" not in sys.modules:
        return
    from euler_tpu import run_loop

    args = run_loop.define_flags().parse_args(
        ["--model", "graphsage_supervised", "--max_id", "1",
         "--alias_sampling", "true"])
    if getattr(run_loop.build_model(args, None), "sampling_alias", False):
        return
    raise SystemExit(
        "reddit power-law reference: the program's run_loop.build_model "
        "does not apply --alias_sampling (a program from before the cell, "
        "which applies it in main() alone); its harness would build a "
        "padded slab as wide as the graph's largest degree (about 340 GB "
        "at 2,090,001 x 20,248); this cell cannot run on it; no result")


refuse_program_without_alias_form()
