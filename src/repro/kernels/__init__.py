# Pallas TPU kernels for the perf-critical hot spots:
#   knn_topk            — the paper's batched estimator lookup (§4.2/§6.3)
#   decode_attention    — flash-decoding GQA step (serving substrate)
#   ssd_scan            — mamba2 SSD chunked scan (assigned arch)
#   decision_megakernel — the whole fused routing decision (KNN top-k →
#                         packed GBM → Eq. 2 admission → LPT greedy
#                         scan) as one kernel, K windows per dispatch
# ops.py = jit'd wrappers (Mosaic on a TPU, the interpreter elsewhere);
# ref.py = pure oracles.
from . import ops as knn_ops  # noqa: F401  (KNNEstimator pallas backend)
# import the decision_megakernel SUBMODULE before binding the same-named
# wrapper function: a later `import repro.kernels.decision_megakernel`
# would otherwise silently rebind the package attribute to the module,
# shadowing the function for everyone after it
from . import decision_megakernel as _decision_megakernel_module  # noqa: F401,E501
from .ops import (decision_megakernel, decode_attention,  # noqa: F401
                  knn_topk, ssd_scan)
