"""mxnet_tpu.serving — the inference fast path.

Four layers, composable (docs/inference.md and
docs/serving_resilience.md are the guides):

  - `BucketSpec` / `buckets` — the padded shape-bucket lattice
    (pow2-derived, `MXNET_SERVE_BUCKETS` / `MXNET_SERVE_SEQ_BUCKETS`);
  - `BucketedPredictor` — AOT-compiled executables per bucket
    (`jax.jit(...).lower(...).compile()`), `warmup()` for zero
    hot-path compiles, donated input buffers, persistent compile cache
    via `JAX_COMPILATION_CACHE_DIR`;
  - `MicroBatcher` — dynamic micro-batching: concurrent requests
    coalesce into one covering-bucket dispatch
    (`MXNET_SERVE_MAX_WAIT_MS` / `MXNET_SERVE_MAX_BATCH`);
  - `ResilientServer` — the resilience tier: per-tenant admission
    control with bounded priority queues (`MXNET_SERVE_MAX_QUEUE`),
    deadline-aware scheduling + load shedding
    (`MXNET_SERVE_SHED_POLICY`, typed `Overloaded` /
    `DeadlineExceeded`), and a `healthz()`/`readyz()` surface fed from
    the metrics registry.  Failure behavior is testable via
    `mxnet_tpu.faultinject`.
  - `ModelRegistry` — N models in one process under an HBM budget
    (`MXNET_HBM_BUDGET_MB`, `MXNET_SERVE_MAX_MODELS`): LRU eviction of
    cold buckets then cold models (`MXNET_SERVE_EVICT_POLICY`),
    restart-free readmission via the persistent compile cache, a typed
    degradation ladder ending in `ModelUnavailable` with retry-after,
    and tenant→model routing through each model's bounded queues
    (docs/multi_model.md).

Every request is flight-recorded end to end (ISSUE 8,
docs/observability.md): a trace_id minted at submit rides through
submit/admission -> queue-wait -> pad -> dispatch -> slice phase spans
across the batcher/scheduler threads, the serving latency histogram
carries per-bucket exemplar trace ids, and a slow-request watchdog
auto-dumps a Perfetto-loadable timeline on anomaly
(`observability.flight`; `MXNET_FLIGHT=0` disables).

Reference lineage: the C predict API + bucketing executors of MXNet
(arxiv 1512.01274), TVM's ahead-of-time deployment modules
(arxiv 1802.04799), and TF-Serving's health-checked batching workers
(arxiv 1605.08695).
"""
from . import buckets
from .buckets import (BucketSpec, covering_bucket, pad_to_shape,
                      parse_bucket_env, pow2_buckets)
from .predictor import BucketedPredictor, ModelEvictedError
from .batcher import (BatcherClosedError, BatcherDeadError, MicroBatcher,
                      stack_requests)
from . import resilience
from .resilience import DeadlineExceeded, Overloaded, ResilientServer
from . import registry
from .registry import ModelRegistry, ModelUnavailable
from . import decode
from .decode import (CellModel, DecodeEngine, GenerativeRouteError,
                     SequenceEvicted, ToyLM)

__all__ = ["BucketSpec", "BucketedPredictor", "MicroBatcher",
           "ResilientServer", "Overloaded", "DeadlineExceeded",
           "BatcherClosedError", "BatcherDeadError", "buckets",
           "resilience", "covering_bucket", "pad_to_shape",
           "parse_bucket_env", "pow2_buckets", "stack_requests",
           "registry", "ModelRegistry", "ModelUnavailable",
           "ModelEvictedError", "decode", "DecodeEngine", "ToyLM",
           "CellModel", "GenerativeRouteError", "SequenceEvicted"]
