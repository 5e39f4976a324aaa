"""Continuous stage-level micro-batching serving subsystem.

Layers (see ROADMAP.md "Serving architecture"):
  batcher     BatchTimeModel (per-bucket stage WCETs) + StageBatcher
              (greedy deadline-feasible batch formation)          [no jax]
  policy      BatchPolicy contract + BatchedPolicy adapter        [no jax]
  admission   AdmissionController (reject / depth-cap)            [no jax]
  stage_fns   padded, shape-bucketed jitted stage functions
"""
from repro.serving.batch.admission import (AdmissionController,
                                           AdmissionDecision)
from repro.serving.batch.batcher import (DEFAULT_BUCKETS, BatchTimeModel,
                                         StageBatcher, bucket_for)
from repro.serving.batch.policy import (BatchedPolicy, BatchPolicy,
                                        as_batch_policy)
from repro.serving.batch.stage_fns import (BatchedStageFns, StagingBuffers,
                                           pad_batch,
                                           profile_batched_stages,
                                           split_rows)
from repro.serving.batch.time_model import (DEFAULT_LEN_BUCKETS,
                                            LengthBucketTimeModel,
                                            batch_wcet, len_bucket_for,
                                            task_len_bucket)

__all__ = [
    "AdmissionController", "AdmissionDecision", "BatchTimeModel",
    "BatchedPolicy", "BatchPolicy",
    "BatchedStageFns", "DEFAULT_BUCKETS", "DEFAULT_LEN_BUCKETS",
    "LengthBucketTimeModel", "StageBatcher", "StagingBuffers",
    "as_batch_policy", "batch_wcet", "bucket_for", "len_bucket_for",
    "pad_batch", "profile_batched_stages",
    "split_rows", "task_len_bucket",
]
