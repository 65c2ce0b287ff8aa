"""ProcessMesh — an N-D cartesian arrangement of devices with named axes.

Analog of the reference's
/root/reference/paddle/phi/core/distributed/auto_parallel/process_mesh.h:34
and python/paddle/distributed/auto_parallel/process_mesh.py. The TPU-native
backing object is ``jax.sharding.Mesh``: mesh axis names become the names
used by ``PartitionSpec``/``NamedSharding`` and by in-program collectives
(``lax.psum(..., axis_name)``), which XLA lowers onto ICI/DCN.

Unlike the reference (one process per device, SPMD multi-process), jax is
single- or multi-controller: ``process_ids`` here index the global
``jax.devices()`` list rather than OS processes.
"""
from __future__ import annotations

import numpy as np

__all__ = ["ProcessMesh", "get_mesh", "set_mesh", "auto", "init_mesh"]


class ProcessMesh:
    def __init__(self, mesh, dim_names=None, shape=None, process_ids=None):
        if mesh is not None:
            arr = np.asarray(mesh, dtype=np.int64)
        else:
            arr = np.asarray(process_ids, dtype=np.int64).reshape(shape)
        if dim_names is None:
            dim_names = [f"d{i}" for i in range(arr.ndim)]
        if len(dim_names) != arr.ndim:
            raise ValueError(
                f"dim_names {dim_names} does not match mesh ndim {arr.ndim}"
            )
        if len(set(dim_names)) != len(dim_names):
            raise ValueError(f"duplicate dim_names {dim_names}")
        self._mesh = arr
        self._dim_names = list(dim_names)
        self._jax_mesh = None

    # ------------------------------------------------ metadata

    @property
    def shape(self):
        return list(self._mesh.shape)

    @property
    def ndim(self):
        return self._mesh.ndim

    @property
    def dim_names(self):
        return list(self._dim_names)

    @property
    def process_ids(self):
        return self._mesh.flatten().tolist()

    @property
    def mesh(self):
        return self._mesh

    def get_dim_size(self, dim_name: str) -> int:
        return self._mesh.shape[self._dim_names.index(dim_name)]

    def get_rank_by_dim_and_process_id(self, dim_name, process_id):
        axis = self._dim_names.index(dim_name)
        where = np.argwhere(self._mesh == process_id)
        if where.size == 0:
            return -1
        return int(where[0][axis])

    def __contains__(self, process_id: int):
        return bool((self._mesh == process_id).any())

    def __eq__(self, other):
        return (
            isinstance(other, ProcessMesh)
            and self._dim_names == other._dim_names
            and np.array_equal(self._mesh, other._mesh)
        )

    def __hash__(self):
        return hash((tuple(self._dim_names), self._mesh.tobytes(), self._mesh.shape))

    def __repr__(self):
        return f"ProcessMesh(shape={self.shape}, dim_names={self._dim_names})"

    # ------------------------------------------------ jax backing

    def jax_mesh(self):
        """The backing ``jax.sharding.Mesh`` (built lazily: device discovery
        first touches the TPU runtime and, on the chip, takes the chip
        for this process — nothing that only builds a mesh description
        should pay for or hold it)."""
        if self._jax_mesh is None:
            import jax
            from jax.sharding import Mesh

            devices = jax.devices()
            ids = self._mesh.flatten()
            if int(ids.max()) >= len(devices):
                raise RuntimeError(
                    f"ProcessMesh needs device id {int(ids.max())} but only "
                    f"{len(devices)} jax devices are visible; set "
                    f"XLA_FLAGS=--xla_force_host_platform_device_count=N for "
                    f"virtual CPU meshes"
                )
            dev_arr = np.array([devices[i] for i in ids]).reshape(self._mesh.shape)
            self._jax_mesh = Mesh(dev_arr, tuple(self._dim_names))
        return self._jax_mesh

    def get_group(self, dim_name=None):
        from .collective import Group

        if dim_name is None:
            return Group(ranks=self.process_ids, mesh=self, axis=None)
        return Group(ranks=self.process_ids, mesh=self, axis=dim_name)

    def get_mesh_with_dim(self, dim_name, index=None):
        """Sub-mesh: move ``dim_name`` to the front, optionally index into it
        (reference process_mesh.py get_mesh_with_dim)."""
        axis = self._dim_names.index(dim_name)
        order = [axis] + [i for i in range(self.ndim) if i != axis]
        new_mesh = self._mesh.transpose(order)
        new_names = [self._dim_names[i] for i in order]
        if index is None:
            return ProcessMesh(new_mesh, new_names)
        return ProcessMesh(new_mesh[index], new_names[1:])


_global_mesh: ProcessMesh | None = None


def set_mesh(mesh: ProcessMesh | None):
    """Install the global mesh (reference auto_parallel.set_mesh);
    ``None`` removes it."""
    global _global_mesh
    _global_mesh = mesh


def get_mesh() -> ProcessMesh | None:
    return _global_mesh


def init_mesh(dim_names=("dp",), shape=None):
    """Convenience: build a mesh over all visible devices and install it."""
    import jax

    n = len(jax.devices())
    if shape is None:
        shape = [n] + [1] * (len(dim_names) - 1)
    mesh = ProcessMesh(np.arange(n).reshape(shape), list(dim_names))
    set_mesh(mesh)
    return mesh


def auto(shape=None, dim_names=None):  # reference dist.auto placeholder
    return init_mesh(dim_names or ("dp",), shape)
