# The paper's primary contribution: balance-aware execution. Amdahl/roofline
# analysis (amdahl.py, hlo_analysis.py, balance.py) + the three mitigation
# techniques recast for TPU (compression.py = LZO, buckets.py = output
# buffering, collectives.py = shared-memory-vs-TCP locality).
from repro.core.amdahl import (
    RooflineTerms, PEAK_FLOPS, HBM_BW, ICI_BW, ICI_LINKS_PER_CHIP, CROSS_POD_BW,
    model_flops_train, model_flops_prefill, model_flops_decode,
)
from repro.core.balance import balance_report, suggest
from repro.core.buckets import BucketPlan, make_plan, flatten, unflatten
from repro.core.collectives import hierarchical_psum_1d, flat_psum
from repro.core.compression import (
    quantize_block, dequantize_block, compress_roundtrip, ef_compress,
    compressed_psum_1d,
)
from repro.core.hlo_analysis import (
    parse_collectives, collective_summary, op_census,
)
