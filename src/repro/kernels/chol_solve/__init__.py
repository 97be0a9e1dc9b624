from repro.kernels.chol_solve.kernel import chol_solve  # noqa: F401
