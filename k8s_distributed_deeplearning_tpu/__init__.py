"""k8s_distributed_deeplearning_tpu — a TPU-native distributed deep-learning framework.

A ground-up JAX/XLA re-design of the capability surface of the reference
``MuhamedAyoub/k8s-distributed-deeplearning`` stack (Horovod + OpenMPI + Kubeflow
MPI Operator + Loki observability on Kubernetes):

- ``parallel``  — device meshes, data/tensor/FSDP sharding, the data-parallel
  engine (the Horovod ``DistributedOptimizer`` replacement, incl. Adasum), and
  the multi-host runtime (the mpirun/OpenMPI replacement:
  ``jax.distributed.initialize`` wired from env vars injected by the K8s
  controller).
- ``models``    — model zoo (MNIST ConvNet parity model, ResNet, BERT, ViT,
  Llama-style transformer, MoE).
- ``ops``       — collectives (psum/all_gather/ppermute-based reductions,
  Adasum, ring attention) and Pallas TPU kernels.
- ``train``     — training loop with hooks, sharded data pipeline, Orbax
  checkpointing with restore-on-start.
- ``utils``     — structured JSONL metrics (the Loki/Promtail-facing surface),
  logging.
- ``launch``    — TPUJob manifest renderer (the MPIJob CRD / deploy_stack.sh
  replacement).
- ``runtime``   — bindings to the native C++ runtime components (gradient
  bucket fusion planner, collective probe; parity with Horovod's C++ core).

Reference capability map: see SURVEY.md at the repo root; per-module docstrings
cite the reference files (``file:line``) they provide parity for.
"""

__version__ = "0.1.0"

from k8s_distributed_deeplearning_tpu import config as config  # noqa: F401
