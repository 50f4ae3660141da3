from repro.kernels.leapfrog.ops import seismograms, solver_path  # noqa: F401
