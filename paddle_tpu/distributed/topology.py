"""Hybrid-parallel topology over a jax.sharding.Mesh.

Reference: `CommunicateTopology`/`HybridCommunicateGroup`
(python/paddle/distributed/fleet/base/topology.py:52,134) building the 4-D rank mesh
[dp, pp, sharding, mp] and per-axis comm groups.  TPU-native: the rank mesh IS a
jax.sharding.Mesh whose axes are the parallelism dimensions; "comm groups" become
named mesh axes that collectives reference inside jit/shard_map.  Axis order follows
the reference's hybrid_configs convention plus net-new 'sep' (sequence parallel).
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh

from . import env as _env
from .collective import Group, new_group

# canonical axis order (outermost first): pp slowest, mp innermost like the reference
AXIS_ORDER = ("pp", "dp", "sharding", "sep", "mp")


class CommunicateTopology:
    """Ref topology.py:52."""

    def __init__(self, hybrid_group_names=("data", "pipe", "sharding", "model"),
                 dims=(1, 1, 1, 1)):
        self._parallel_names = list(hybrid_group_names)
        self._dims = list(dims)
        self.coordinate = None
        self._world = int(np.prod(self._dims))

    def get_hybrid_group_names(self):
        return self._parallel_names

    def get_dim(self, axis_name):
        return self._dims[self._parallel_names.index(axis_name)]

    def world_size(self):
        return self._world

    def get_rank(self, **kwargs):
        coords = [kwargs[n] for n in self._parallel_names]
        return int(np.ravel_multi_index(coords, self._dims))

    def get_coord(self, rank):
        return tuple(int(i) for i in np.unravel_index(rank, self._dims))

    def get_axis_list(self, axis_name, index):
        axis = self._parallel_names.index(axis_name)
        ranks = [r for r in range(self._world) if self.get_coord(r)[axis] == index]
        return ranks

    def get_dim_size(self, axis_name):
        return self.get_dim(axis_name)

    def get_comm_list(self, axis_name):
        axis = self._parallel_names.index(axis_name)
        other_dims = [d for i, d in enumerate(self._dims) if i != axis]
        lists = []
        for flat in range(int(np.prod(other_dims)) if other_dims else 1):
            coords = list(np.unravel_index(flat, other_dims)) if other_dims else []
            group = []
            for k in range(self._dims[axis]):
                full = coords[:axis] + [k] + coords[axis:]
                group.append(self.get_rank(**dict(zip(self._parallel_names, full))))
            lists.append(group)
        return lists


def build_mesh(dp=1, mp=1, pp=1, sharding=1, sep=1, devices=None) -> Mesh:
    """Create the device mesh for a hybrid strategy.  Axis layout puts mp
    innermost so tensor-parallel collectives ride the fastest ICI links
    (scaling-book recipe).

    When the mesh uses EVERY visible TPU device the assignment goes through
    mesh_utils.create_device_mesh (single slice: ICI-aware placement per
    axis) or create_hybrid_device_mesh (multi-host with DCN: the outermost
    data axes span hosts, mp/sep stay inside a slice); a shape mesh_utils
    cannot place raises — pass `devices=` to choose the order yourself.  A
    mesh over a subset of the visible devices, an explicit `devices=` list
    and every non-TPU backend take the devices in the order given."""
    shape = (pp, dp, sharding, sep, mp)
    need = int(np.prod(shape))
    if devices is None:
        all_devs = jax.devices()
        if all_devs[0].platform == "tpu" and len(all_devs) == need:
            from jax.experimental import mesh_utils

            n_hosts = max(getattr(d, "process_index", 0) for d in all_devs) + 1
            if n_hosts > 1:
                per_host = len(all_devs) // n_hosts
                # split each axis into a DCN (cross-host) and ICI part:
                # data-like axes absorb the host dimension outermost
                dcn = [1] * len(shape)
                ici = list(shape)
                rest = n_hosts
                for i in (1, 2, 0):        # dp, sharding, then pp over DCN
                    g = int(np.gcd(ici[i], rest))
                    dcn[i] *= g
                    ici[i] //= g
                    rest //= g
                    if rest == 1:
                        break
                if rest == 1 and per_host == int(np.prod(ici)):
                    dev = mesh_utils.create_hybrid_device_mesh(
                        tuple(ici), tuple(dcn), devices=all_devs)
                    return Mesh(dev, AXIS_ORDER)
            dev = mesh_utils.create_device_mesh(shape, devices=all_devs)
            return Mesh(dev, AXIS_ORDER)
        devices = np.array(all_devs)
    if len(devices) < need:
        raise ValueError(f"need {need} devices, have {len(devices)}")
    dev = np.asarray(devices)[:need].reshape(shape)
    return Mesh(dev, AXIS_ORDER)


class HybridCommunicateGroup:
    """Ref topology.py:134.  Wraps a Mesh; exposes the reference's group getters."""

    def __init__(self, topology=None, dp=None, mp=None, pp=None, sharding=None, sep=1):
        if topology is not None and dp is None:
            dims = {n: topology.get_dim(n) for n in topology.get_hybrid_group_names()}
            dp = dims.get("data", 1)
            mp = dims.get("model", 1)
            pp = dims.get("pipe", 1)
            sharding = dims.get("sharding", 1)
        self._dp_degree = dp or 1
        self._mp_degree = mp or 1
        self._pp_degree = pp or 1
        self._sharding_degree = sharding or 1
        self._sep_degree = sep or 1
        self._topo = topology
        total = self._dp_degree * self._mp_degree * self._pp_degree * self._sharding_degree * self._sep_degree
        n_dev = len(jax.devices())
        self.mesh = None
        if total <= n_dev:
            self.mesh = build_mesh(self._dp_degree, self._mp_degree, self._pp_degree,
                                   self._sharding_degree, self._sep_degree)
        self.global_rank = _env.get_rank()
        self._dp_group = new_group(axis_name="dp")
        self._mp_group = new_group(axis_name="mp")
        self._pp_group = new_group(axis_name="pp")
        self._sharding_group = new_group(axis_name="sharding")
        self._sep_group = new_group(axis_name="sep")

    # --- degree / rank getters (ref topology.py get_*_parallel_*)
    def get_data_parallel_world_size(self):
        return self._dp_degree

    def get_model_parallel_world_size(self):
        return self._mp_degree

    def get_pipe_parallel_world_size(self):
        return self._pp_degree

    def get_sharding_parallel_world_size(self):
        return self._sharding_degree

    def get_sep_parallel_world_size(self):
        return self._sep_degree

    def _coord(self):
        """This process's coordinate in the mesh = coordinate of its first
        addressable device (per-rank coordinates only exist at process
        granularity on TPU; within a process SPMD materializes them inside
        shard_map).  Single-process: (0,0,0,0,0)."""
        if self.mesh is not None and jax.process_count() > 1:
            local_ids = {d.id for d in jax.local_devices()}
            devs = self.mesh.devices
            for idx in np.ndindex(devs.shape):
                if devs[idx].id in local_ids:
                    return tuple(int(i) for i in idx)
        return (0, 0, 0, 0, 0)

    def get_data_parallel_rank(self):
        return self._coord()[1]

    def get_model_parallel_rank(self):
        return self._coord()[4]

    def get_stage_id(self):
        return self._coord()[0]

    def get_sharding_parallel_rank(self):
        return self._coord()[2]

    def get_data_parallel_group(self):
        return self._dp_group

    def get_model_parallel_group(self):
        return self._mp_group

    def get_pipe_parallel_group(self):
        return self._pp_group

    def get_sharding_parallel_group(self):
        return self._sharding_group

    def get_sep_parallel_group(self):
        return self._sep_group

    def get_check_parallel_group(self, *a, **k):
        return self._mp_group

    def get_rank_from_stage(self, stage_id, **kwargs):
        """PROCESS rank owning pipeline stage `stage_id` at this process's
        other coordinates (overridable via kwargs, ref topology.py).  On a
        multi-device-per-process mesh this is the owning process index, not a
        per-device ordinal."""
        coord = list(self._coord())
        coord[0] = stage_id
        for i, name in enumerate(("pp", "dp", "sharding", "sep", "mp")):
            if name in kwargs:
                coord[i] = kwargs[name]
        if self.mesh is not None:
            dev = self.mesh.devices[tuple(coord)]
            return int(getattr(dev, "process_index", 0))
        dims = (self._pp_degree, self._dp_degree, self._sharding_degree,
                self._sep_degree, self._mp_degree)
        return int(np.ravel_multi_index(coord, dims))

    def topology(self):
        return self._topo


_hcg: HybridCommunicateGroup | None = None


def get_hybrid_communicate_group():
    return _hcg


def set_hybrid_communicate_group(hcg):
    global _hcg
    _hcg = hcg
