"""ddw_tpu — a TPU-native distributed deep-learning framework.

A brand-new, TPU-first (JAX / XLA / pjit / Pallas) framework providing, in-tree, the
capability stack of the s-udhaya/distributed-deep-learning-workshop reference
(Spark + Delta Lake + Petastorm + TF/Keras + Horovod + Hyperopt + MLflow):

- ``ddw_tpu.data``      — sharded binary-image table store, data-prep pipeline, and a
                          per-host sharded loader with infinite-repeat semantics
                          (Delta Lake + Petastorm roles).
- ``ddw_tpu.models``    — flax CNN model zoo (MobileNetV2-class transfer learning,
                          SmallCNN, ViT) as pure init/apply functions.
- ``ddw_tpu.train``     — jitted SPMD train step + trainer + callback suite (LR warmup,
                          plateau, early stop, metric averaging) (TF/Keras fit +
                          Horovod callback roles).
- ``ddw_tpu.runtime``   — device mesh, collectives, multihost launcher
                          (Horovod core + HorovodRunner roles).
- ``ddw_tpu.parallel``  — named-axis sharding strategies: data / tensor / sequence
                          (ring attention) / pipeline axes over a ``jax.sharding.Mesh``
                          (in progress this round).
- ``ddw_tpu.ops``       — Pallas TPU kernels for hot ops (in progress this round).
- ``ddw_tpu.checkpoint``— step-indexed checkpoint/resume with rank-0 writer discipline.
- ``ddw_tpu.tune``      — in-tree TPE hyperparameter search with parallel and
                          sequential-over-distributed trial executors (Hyperopt role)
                          (in progress this round).
- ``ddw_tpu.tracking``  — file-based experiment tracker + model registry with stage
                          transitions (MLflow tracking/registry roles).
- ``ddw_tpu.serving``   — packaged-model format + distributed batch scorer
                          (MLflow pyfunc / spark_udf roles) (in progress this round).

The behavioral contract is documented in SURVEY.md; reference file:line
citations appear in each module's docstring.
"""

__version__ = "0.1.0"

from ddw_tpu.utils.config import (  # noqa: F401
    DataCfg,
    ModelCfg,
    TrainCfg,
    TuneCfg,
)
