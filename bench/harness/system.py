"""The system under test: a ``Session`` configured as the deployment file
says (its ``session`` arguments, and a mesh where it names one), the
datasets in the configured layout, and an ``AFrame`` over each. Nothing
here measures or checks."""
from __future__ import annotations

import jax
import numpy as np

DATAVERSE = "bench"


def _arrays(ds) -> list:
    out = list(ds.table.columns.values())
    for ix in ds.indexes.values():
        out += [ix.sorted_keys, ix.row_ids]
    return out


class System:
    def __init__(self, config: dict):
        from repro.engine.session import Session

        self.config = config
        mesh = None
        if config.get("mesh"):  # e.g. {"data": 4, "model": 1}
            from repro.launch.mesh import make_local_mesh

            mesh = make_local_mesh(**config["mesh"])
        self.session = Session(mesh=mesh, **config["session"])
        self.frames: dict = {}

    def load(self, name: str, cols: dict, stats: dict) -> None:
        """One dataset in the configured layout; returns once every array
        of it is on the device."""
        from repro.core.frame import AFrame
        from repro.engine.table import ColumnMeta, Table

        meta = {k: ColumnMeta(np.dtype(np.uint8) if s.get("is_string")
                              else np.dtype(cols[k].dtype), **s)
                for k, s in stats.items()}
        layout = self.config["layout"]
        ds = self.session.create_dataset(
            name, Table(cols, meta), dataverse=DATAVERSE,
            closed=layout["closed"], indexes=layout["indexes"],
            primary=layout["primary"])
        jax.block_until_ready(_arrays(ds))
        self.frames[name] = AFrame(DATAVERSE, name, session=self.session)

    def last_physical(self):
        return getattr(self.session, "last_physical", None)

    def compiles(self) -> int:
        return int(self.session.stats["compiles"])

    def memory_peak_bytes(self) -> int:
        """The peak on the fullest chip."""
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    def close(self) -> None:
        if self.session is not None:
            self.session.close()
            self.session = None
        self.frames.clear()
