from repro.kernels.mat_lut.ops import (
    envelope_reason,
    mat_classify,
    mat_classify_reference,
    MAX_BINS,
    MAX_FEATURES,
)
from repro.kernels.mat_lut.ref import mat_pipeline_ref
from repro.kernels.mat_lut.kernel import vmem_bytes, LANE
