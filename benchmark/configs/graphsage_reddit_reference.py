"""This configuration's plain reference and adapter: supervised GraphSAGE,
two hops, mean aggregator (``benchmark/sage_reference.py``, whose
docstring states what each function of the protocol does). The
configuration file beside this one gives the sizes and the loss
(``sigmoid_loss``: softmax cross-entropy over 41 classes).

What the harness and ``check.py`` call here, all of it: ``init_state``,
``drawn_fanouts``, ``drawn_hops``, ``reference_batch``, ``batch_rows``,
``first_gradient``, ``compared_state``, ``train_steps``. The rest is the
reference's own mathematics, kept importable for the tests. The work a
step needs by shape is ``graphsage_costs.py`` (``"costs"`` in the file)."""

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
