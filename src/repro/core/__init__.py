"""The paper's contribution: UGA (§3.1) + FedMeta (§3.2) as composable,
model- and task-agnostic strategies over arbitrary JAX models — exposed
through three plugin registries plus a facade:

  * :mod:`repro.core.algorithms` — ClientAlgorithm registry (what a client
    computes): uga / fedavg / fedprox / fednova / register your own;
  * :mod:`repro.core.executors` — CohortExecutor registry (how the cohort
    runs): chunked / vmap / scan / sharded over one streaming core,
    yielding uniform aggregate handles;
  * :mod:`repro.core.engines` — ServerEngine registry (what the server
    does with the aggregate): legacy_tree / fused_flat, with declared
    FedMeta capabilities;
  * :class:`repro.core.trainer.FederatedTrainer` — the one driver loop
    (jit cache, chunked sampling, checkpoint/resume, history).

``make_federated_round`` / ``init_server_state`` / ``RoundFnCache`` /
``stack_round_inputs`` keep their pre-registry signatures (thin
compositions over the registries) so existing callers run unmodified.
"""
from repro.core.aggregate import (chunked_cohort_gradient_flat,
                                  scan_cohort_deltas_flat)
from repro.core.async_round import (init_async_state, make_async_tick,
                                    resolve_async_shape, staleness_discount)
from repro.core.algorithms import (available_algorithms, get_algorithm,
                                   register_algorithm)
from repro.core.client import (fedavg_update, make_client_update, uga_update)
from repro.core.engines import (available_engines, get_engine,
                                register_engine, resolve_engine)
from repro.core.executors import (available_executors, get_executor,
                                  register_executor, resolve_executor)
from repro.core.meta import meta_update, meta_update_through_cohort
from repro.core.round import (init_server_state, make_federated_round,
                              grad_global_norm, participation_mask,
                              resolve_server_lr, RoundFnCache,
                              stack_round_inputs)
from repro.core.trainer import FederatedTrainer
from repro.core import server_opt

__all__ = ["chunked_cohort_gradient_flat", "scan_cohort_deltas_flat",
           "init_async_state", "make_async_tick", "resolve_async_shape",
           "staleness_discount",
           "fedavg_update", "uga_update",
           "make_client_update", "meta_update",
           "meta_update_through_cohort", "init_server_state",
           "make_federated_round", "grad_global_norm", "participation_mask",
           "resolve_server_lr", "server_opt", "RoundFnCache",
           "stack_round_inputs",
           "register_algorithm", "get_algorithm", "available_algorithms",
           "register_executor", "get_executor", "available_executors",
           "resolve_executor",
           "register_engine", "get_engine", "available_engines",
           "resolve_engine",
           "FederatedTrainer"]
