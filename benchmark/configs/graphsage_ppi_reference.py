"""This configuration's plain reference: supervised GraphSAGE, two hops,
mean aggregator (``benchmark/sage_reference.py``). The configuration file
beside this one gives the sizes and the loss (``sigmoid_loss``)."""

from benchmark.sage_reference import (  # noqa: F401
    adam_init,
    adam_update,
    drawn_hops,
    first_gradient,
    from_program,
    init_params,
    loss_fn,
    param_shapes,
    to_program,
    train_steps,
)
