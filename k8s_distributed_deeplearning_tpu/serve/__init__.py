"""Continuous-batching serving (iteration-level scheduling over a paged KV
pool).

The one-shot :func:`models.generate.generate` path pins a batch's wall-clock
to its longest request; this package serves mixed-length traffic through ONE
shape-static compiled decode step over a persistent paged KV pool — block
tables map each slot's virtual sequence onto refcounted fixed-size pages
(vLLM's PagedAttention layout), so HBM is paid per live token and the
prefix trie shares pages into slots with zero device copies — with freed
slots re-admitted in flight (Orca-style iteration scheduling). See
:mod:`serve.engine` for the design contract.
"""
from k8s_distributed_deeplearning_tpu.serve.autoscale import (
    BROWNOUT_STAGE_NAMES, BrownoutStage, EngineFactoryBackend,
    FleetController, K8sParallelismBackend, LocalProcessBackend,
    default_brownout_stages)
from k8s_distributed_deeplearning_tpu.serve.disagg import (
    DisaggCoordinator, PrefillWorker, RemotePrefillWorker)
from k8s_distributed_deeplearning_tpu.serve.engine import ServeEngine
from k8s_distributed_deeplearning_tpu.serve.gateway import ServeGateway
from k8s_distributed_deeplearning_tpu.serve.page_pool import PagePool
from k8s_distributed_deeplearning_tpu.serve.prefix_cache import PrefixCache
from k8s_distributed_deeplearning_tpu.serve.request import (
    EngineDraining, QueueFull, Request, RequestOutput, SamplingParams)
from k8s_distributed_deeplearning_tpu.serve.sched import (
    DEFAULT_TENANT, TenantConfig, TenantScheduler, load_tenants)
from k8s_distributed_deeplearning_tpu.serve.storm import (
    InvariantMonitor, StormConfig, StormReport, run_storm)
from k8s_distributed_deeplearning_tpu.serve.transport import (
    ReplicaClient, ReplicaServer, discover_replica_clients)

__all__ = ["ServeEngine", "ServeGateway", "Request", "RequestOutput",
           "SamplingParams", "QueueFull", "EngineDraining",
           "PagePool", "PrefixCache", "TenantConfig", "TenantScheduler",
           "DEFAULT_TENANT", "load_tenants", "ReplicaServer",
           "ReplicaClient", "discover_replica_clients",
           "DisaggCoordinator", "PrefillWorker", "RemotePrefillWorker",
           "FleetController", "BrownoutStage", "BROWNOUT_STAGE_NAMES",
           "default_brownout_stages", "EngineFactoryBackend",
           "LocalProcessBackend", "K8sParallelismBackend",
           "StormConfig", "StormReport", "InvariantMonitor", "run_storm"]
