from repro.kernels.flow_update.ops import (
    MAX_HISTS,
    MAX_SLOTS,
    MAX_WIDTH,
    envelope_reason,
    flow_update,
)
from repro.kernels.flow_update.ref import flow_update_ref, hash_slot
from repro.kernels.flow_update.kernel import LANE, vmem_bytes
