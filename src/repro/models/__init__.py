from repro.models.common import CommittedCache, ParallelCtx
from repro.models.exits import exit_rows, exit_stats_fused, exit_stats_unfused
from repro.models.model import (
    DecodeFrom,
    ExitsOut,
    concat_decode_caches,
    count_params_analytic,
    decode_step,
    forward,
    init_decode_cache,
    init_params,
    slice_decode_cache,
    stage_decode_step,
    stage_forward,
    stage_layouts,
    stage_trunk,
    write_decode_slots,
)

__all__ = [
    "CommittedCache", "ParallelCtx", "DecodeFrom", "ExitsOut",
    "concat_decode_caches", "count_params_analytic", "decode_step",
    "exit_rows", "exit_stats_fused", "exit_stats_unfused",
    "forward", "init_decode_cache", "init_params", "slice_decode_cache",
    "stage_decode_step", "stage_forward", "stage_layouts", "stage_trunk",
    "write_decode_slots",
]
