"""Device mesh construction and multi-host initialization.

The reference has NO distributed support (SURVEY.md §2.2: no
torch.distributed / NCCL anywhere). This module is the TPU-native
communication backend: a named `Mesh` over the chip topology, with XLA
emitting collectives over ICI from sharding annotations (pjit/GSPMD) or from
explicit shard_map collectives (ring / halo / all-to-all in this package).

Axis convention (see utils.config.MeshConfig):
  data  — batch sharding (DP); gradient allreduce rides ICI (multi-slice
          setups put the outermost data axis on DCN)
  seq   — patch-axis sharding (SP): ring consensus / halo exchange
  model — dim sharding (TP) of the grouped-FFW weights
"""

from __future__ import annotations

import os
import warnings
from typing import Optional

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from glom_tpu.utils.config import MeshConfig


def make_mesh(cfg: MeshConfig, devices: Optional[list] = None) -> Mesh:
    """Build a Mesh of shape (data, seq, model) over the available devices.

    Uses mesh_utils.create_device_mesh on real TPU slices so mesh axes map
    contiguously onto the ICI torus (nearest-neighbor collectives stay on
    ICI links); CPU/virtual devices take a simple reshape (as does a TPU
    device list create_device_mesh refuses, with a warning).
    """
    devices = devices if devices is not None else jax.devices()
    n = cfg.num_devices
    if n > len(devices):
        raise ValueError(
            f"mesh {cfg.shape} needs {n} devices, only {len(devices)} available"
        )
    devices = devices[:n]
    if cfg.num_slices > 1:
        return _make_hybrid_mesh(cfg, devices)
    if devices[0].platform == "tpu":
        try:
            dev_array = mesh_utils.create_device_mesh(cfg.shape, devices=devices)
        except (ValueError, AssertionError) as e:
            # Enumeration order need not follow the torus: after a plain
            # reshape a mesh axis may hop across ICI links. Never silent
            # on real hardware.
            warnings.warn(
                f"create_device_mesh failed for mesh {cfg.shape} over "
                f"{len(devices)} TPU devices ({e}); falling back to a "
                "reshape of the device list — mesh axes may not be "
                "ICI-contiguous",
                stacklevel=2,
            )
            dev_array = np.asarray(devices).reshape(cfg.shape)
    else:
        dev_array = np.asarray(devices).reshape(cfg.shape)
    return Mesh(dev_array, cfg.axis_names)


def _make_hybrid_mesh(cfg: MeshConfig, devices: list) -> Mesh:
    """Multi-slice (ICI x DCN) mesh: the data axis factors as
    num_slices (outer, DCN) x data/num_slices (inner, ICI); seq and model
    stay intra-slice. The logical mesh keeps the plain (data, seq, model)
    axis names — hierarchy lives entirely in device placement, where XLA
    reads it to emit a reduce-scatter-on-ICI / allreduce-on-DCN
    decomposition for the gradient sync (BASELINE config 5, v5e-256 as
    multi-slice).

    On CPU/virtual devices (and TPU fallback) a slice-major reshape gives
    the same logical layout: device order is assumed slice-contiguous,
    which matches how multi-process virtual harnesses enumerate them.
    """
    s = cfg.num_slices
    ici_shape = (cfg.data // s, cfg.seq, cfg.model)
    dcn_shape = (s, 1, 1)
    if devices[0].platform == "tpu":
        try:
            dev_array = mesh_utils.create_hybrid_device_mesh(
                ici_shape, dcn_shape, devices=devices
            )
        except (ValueError, AssertionError) as e:
            # A raw reshape assumes enumeration order is slice-contiguous;
            # if it is not, seq/model collectives can land on DCN links — a
            # silent order-of-magnitude regression. Never hide this on real
            # hardware.
            warnings.warn(
                f"create_hybrid_device_mesh failed ({e}); falling back to a "
                "slice-major reshape of jax.devices() — verify the device "
                "order is slice-contiguous or intra-slice collectives may "
                "ride DCN",
                stacklevel=3,
            )
            dev_array = np.asarray(devices).reshape(cfg.shape)
    else:
        dev_array = np.asarray(devices).reshape(cfg.shape)
    return Mesh(dev_array, cfg.axis_names)


def replica_device_groups(devices: list, per_replica: int) -> list:
    """Partition `devices` into contiguous groups of `per_replica` — one
    group per serving engine replica (multi-engine fan-out,
    docs/SERVING.md). Contiguous slices keep each replica's mesh on
    neighboring ICI links (jax.devices() enumerates torus-contiguously on
    TPU); leftover devices beyond the last full group are unused rather
    than silently forming an undersized replica."""
    if per_replica < 1:
        raise ValueError(f"per_replica {per_replica} must be >= 1")
    n_groups = len(devices) // per_replica
    if n_groups < 1:
        raise ValueError(
            f"{len(devices)} devices cannot host a {per_replica}-device "
            "replica"
        )
    return [
        devices[i * per_replica : (i + 1) * per_replica]
        for i in range(n_groups)
    ]


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host bring-up: the analog of torch's init_process_group, but via
    the JAX distributed runtime (coordinator + heartbeat failure detection).

    No-op on single-process. Args fall back to the standard env vars
    (JAX_COORDINATOR_ADDRESS, JAX_NUM_PROCESSES, JAX_PROCESS_ID) so launch
    scripts can stay declarative.
    """
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    if coordinator_address is None:
        return  # single host
    kwargs = {"coordinator_address": coordinator_address}
    if num_processes is not None or "JAX_NUM_PROCESSES" in os.environ:
        kwargs["num_processes"] = int(
            num_processes
            if num_processes is not None
            else os.environ["JAX_NUM_PROCESSES"]
        )
    if process_id is not None or "JAX_PROCESS_ID" in os.environ:
        kwargs["process_id"] = int(
            process_id if process_id is not None else os.environ["JAX_PROCESS_ID"]
        )
    jax.distributed.initialize(**kwargs)
