"""User-facing train configs.

reference: python/ray/air/config.py — ScalingConfig :99 (num_workers :154,
use_gpu :155, resources_per_worker :156, accelerator_type :158), RunConfig,
FailureConfig, CheckpointConfig. Per SURVEY §2.3 the rebuild adds ``use_tpu``
and ``topology`` (the reference has no use_tpu).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Optional


@dataclasses.dataclass
class ScalingConfig:
    """How many training workers, with what resources, in what shape.

    TPU semantics: one worker per TPU host (SPMD gang over a slice);
    ``topology`` (e.g. "4x4x8") or ``num_workers`` sizes the gang, and
    ``chips_per_worker`` carves chips (ICI-aligned blocks of 1/2/4/8).
    """

    num_workers: int = 1
    use_tpu: bool = False
    use_gpu: bool = False  # reference-compat; maps onto generic accelerator
    chips_per_worker: Optional[int] = None
    topology: Optional[str] = None
    resources_per_worker: Optional[Dict[str, float]] = None
    accelerator_type: Optional[str] = None
    placement_strategy: str = "PACK"
    tpu_slice: Optional[str] = None  # pin the gang to one named slice

    def worker_resources(self) -> Dict[str, float]:
        res = dict(self.resources_per_worker or {})
        res.setdefault("CPU", 1.0)
        if self.use_tpu and "TPU" not in res:
            chips = self.chips_per_worker
            if chips is None:
                from ray_tpu._private.accelerators import get_accelerator_manager

                chips = get_accelerator_manager(
                    "TPU").get_current_node_num_accelerators()
                if chips <= 0:
                    raise ValueError(
                        "ScalingConfig(use_tpu=True) found no TPU chip on "
                        "this node (no /dev/accel* or /dev/vfio/<n>): set "
                        "chips_per_worker or resources_per_worker to say "
                        "how many each worker holds elsewhere")
            res["TPU"] = float(chips)
        if self.accelerator_type:
            res[f"accelerator_type:{self.accelerator_type}"] = 0.001
        return res

    @property
    def total_workers(self) -> int:
        if self.topology:
            return hosts_in_topology(self.topology, self.chips_per_worker or 4)
        return self.num_workers


def hosts_in_topology(topology: str, chips_per_host: int = 4) -> int:
    """Host count for a TPU topology string like "4x4x8" (chips = product of
    dims; v4/v5p hosts expose 4 chips — reference analog:
    accelerators/tpu.py:316 get_num_workers_in_pod)."""
    import math

    dims = [int(d) for d in topology.lower().split("x")]
    chips = math.prod(dims)
    return max(1, chips // chips_per_host)


@dataclasses.dataclass
class FailureConfig:
    """reference: air/config.py FailureConfig (max_failures)."""

    max_failures: int = 0


@dataclasses.dataclass
class CheckpointConfig:
    """reference: air/config.py CheckpointConfig (num_to_keep, attr ordering).

    TPU-native extension — the continuous async snapshot subsystem
    (train/_internal/snapshot.py), engaged when the train loop reports
    state pytrees (``train.report(metrics, state=...)``):

    - ``full_snapshot_interval``: every Nth snapshot writes ALL leaves;
      the ones between are deltas that reference unchanged leaves in an
      earlier manifest, so the interval bounds how long a delta chain can
      grow (and how much retention must protect).
    - ``optimizer_state_interval``: optimizer-state leaves (top-level key
      in ``optimizer_key_prefixes``) are written every Nth snapshot only;
      in between, delta manifests reference the last written version even
      if it changed — params are still captured every snapshot.
    - ``peer_replicas``: push each member's newest host-RAM shard copy to
      a ring neighbor so a preempted member restores from peer RAM inside
      the drain window instead of from storage.
    """

    num_to_keep: Optional[int] = None
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "max"
    full_snapshot_interval: int = 8
    optimizer_state_interval: int = 1
    peer_replicas: bool = False


@dataclasses.dataclass
class RunConfig:
    """reference: air/config.py RunConfig (name, storage_path, failure/ckpt)."""

    name: Optional[str] = None
    storage_path: Optional[str] = None
    failure_config: Optional[FailureConfig] = None
    checkpoint_config: Optional[CheckpointConfig] = None

    def resolved_storage_path(self) -> str:
        from ray_tpu.train._internal.checkpoint_util import (
            is_remote_path,
            normalize_local_path,
        )

        base = self.storage_path or os.path.expanduser("~/ray_tpu_results")
        if is_remote_path(base):
            return base  # fsspec URI (gs://, s3://, ...): not a local path
        return os.path.abspath(normalize_local_path(base))
