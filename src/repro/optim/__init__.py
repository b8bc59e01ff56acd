from repro.optim.second_order import cg_newton_step  # noqa: F401
