"""paddle_tpu.serving — production serving engine over inference.Predictor.

Reference parity: the reference deploys through AnalysisPredictor +
``Clone()`` fan-out (analysis_predictor.h:82,214) and leaves batching,
warm-up and multi-model management to the application.  On TPU those are
not application details — batch shape is compile shape — so this package
owns them:

  * **continuous batching into bucketed static shapes** (scheduler.py +
    bucketing.py): pending requests pack FIFO into the smallest ladder
    bucket that holds them and pad up; batch size adapts to load with no
    per-request recompiles (Orca-style, the TPU-idiomatic form);
  * **AOT-cache warm-up** (server.py): ``start()`` lints (graph-lint
    admission gate, FLAGS_graph_lint) and compiles every (model, bucket)
    executable before the first request is admitted, each compile
    recorded in the recompile ledger; after the warm-up mark the ledger
    must stay silent — ``assert_zero_steady_state_recompiles()`` proves
    the steady-state invariant;
  * **async host↔device pipelining**: workers keep up to
    FLAGS_serving_pipeline_depth batches in flight, so H2D + dispatch of
    batch N+1 overlap execution of batch N;
  * **clone-per-worker concurrency**: every worker thread serves through
    its own ``Predictor.clone()`` — shared weights and executables,
    per-clone IO buffers.

Gates: ``FLAGS_serving_*`` (framework/flags.py).  CLI: ``tools/serve.py``.
"""
from __future__ import annotations

from .bucketing import BucketLadder, pad_to_bucket  # noqa: F401
from .decode import DecodeModelSpec, DecodeRequest  # noqa: F401
from .scheduler import Batch, Request, RequestQueue, pack_fifo  # noqa: F401
from .server import (ModelSpec, Server, ServingConfig,  # noqa: F401
                     create_server, export_for_serving)
from . import cluster  # noqa: F401  (multi-host disaggregated serving)

__all__ = [
    "BucketLadder", "pad_to_bucket", "Batch", "Request", "RequestQueue",
    "pack_fifo", "ModelSpec", "Server", "ServingConfig", "create_server",
    "export_for_serving", "DecodeModelSpec", "DecodeRequest", "cluster",
]
