"""End-to-end behaviour: the solver driver runs to completion with a small
residual for every method and in float64."""
from repro.launch import solve as solve_cli


def test_solve_driver_all_methods():
    for method in ("lu", "cholesky", "cg", "ca_cg", "ca_gmres",
                   "bicgstab", "gmres"):
        res = solve_cli.main(["--n", "192", "--method", method,
                              "--block-size", "64", "--tol", "1e-8"])
        assert res < 1e-4


def test_solve_driver_fp64():
    res = solve_cli.main(["--n", "128", "--method", "lu",
                          "--dtype", "float64", "--block-size", "32"])
    assert res < 1e-10
