from repro.kernels.jacobi_eigh.kernel import jacobi_eigh  # noqa: F401
