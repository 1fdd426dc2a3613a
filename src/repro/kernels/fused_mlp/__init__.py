from repro.kernels.fused_mlp.ops import (
    envelope_reason,
    fused_dag,
    fused_dag_reference,
    fused_mlp,
    fused_mlp_classify,
    fused_mlp_reference,
)
from repro.kernels.fused_mlp.kernel import (
    DAG_BLOCK_B,
    DAG_VMEM_BUDGET,
    LANE,
    dag_vmem_bytes,
    eval_dag_plan,
    pack_params,
    snap_lane,
    vmem_bytes,
)
