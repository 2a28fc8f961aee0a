from repro.kernels.codr_gmm.ops import codr_gmm

__all__ = ["codr_gmm"]
