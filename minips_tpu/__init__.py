"""minips_tpu — a TPU-native parameter-server training framework.

A ground-up rebuild of the capabilities of the C++ parameter server
``Distributed-Deep-Learning/MiniPs`` (see SURVEY.md; the reference mount was
empty this round — SURVEY.md §0 — so reference citations point at the survey's
component inventory rather than file:line), designed TPU-first:

- Worker compute is ``jax.jit``'d on TPU instead of Eigen/CUDA worker math
  (SURVEY.md §2 "Worker compute").
- The ``KVClientTable`` push/pull API (SURVEY.md §2 "KVClientTable") is kept
  as the user-facing surface, but ``pull`` compiles to an all-gather and
  ``push`` to a reduce-scatter + owner-shard optimizer update over the
  device mesh — XLA collectives over ICI/DCN replace the ZeroMQ Mailbox
  (SURVEY.md §2.3).
- Server-side KVTable + SGD/Adagrad updaters (SURVEY.md §2 "KVTable
  storage", "Updaters") live as pjit-sharded optimizer state.
- The BSP/SSP/ASP consistency controller (SURVEY.md §2 "BSPModel/SSPModel/
  ASPModel") gates collective sync steps instead of parking socket RPCs.
"""

__version__ = "0.1.0"

# The top level is the fused path (core) only: the wire fleet is imported from
# its own modules, and nothing here or below imports it (docs/architecture.md).
from minips_tpu.core.config import Config, TableConfig, TrainConfig  # noqa: F401
from minips_tpu.core.engine import Engine, Info, MLTask  # noqa: F401
from minips_tpu.consistency import ASP, BSP, SSP, make_controller  # noqa: F401
from minips_tpu.parallel.mesh import make_mesh  # noqa: F401
from minips_tpu.tables.dense import DenseTable, cast_floating  # noqa: F401
from minips_tpu.tables.sparse import SparseTable  # noqa: F401
from minips_tpu.train.loop import TrainLoop  # noqa: F401
from minips_tpu.train.ps_step import PSTrainStep  # noqa: F401
from minips_tpu.utils.evaluation import (StreamingAUC,  # noqa: F401
                                         auc_exact, evaluate_auc)
from minips_tpu.utils.metrics import MetricsLogger  # noqa: F401
from minips_tpu.parallel import cluster  # noqa: F401  (multi-host bootstrap)
