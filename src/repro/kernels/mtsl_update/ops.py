"""jit'd wrapper for the fused MTSL update kernel."""
from __future__ import annotations

from repro.kernels.mtsl_update.kernel import mtsl_update_fwd
from repro.kernels.platform import interpret_default


def mtsl_update(p, g, eta):
    """p <- p - eta * g (eta scalar). Pallas-fused on TPU; interpret on CPU."""
    return mtsl_update_fwd(p, g, eta, interpret=interpret_default())
