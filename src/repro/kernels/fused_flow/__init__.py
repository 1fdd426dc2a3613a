from repro.kernels.fused_flow.kernel import (
    LANE,
    READOUT_MODES,
    SUFFIX_KINDS,
    MitPlan,
    Plan,
    SuffixPlan,
    TablePlan,
    forest_vmem_bytes,
    fused_flow_serve_padded,
    suffix_readout,
    suffix_verdicts,
)
from repro.kernels.fused_flow.ops import fused_flow_classify, fused_flow_serve
