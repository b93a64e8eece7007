"""The benchmark's side of the program's interface: registering a
configuration file as an architecture, building the session a cell
asks for, and reading per-leaf norms out of the program's trees."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from model import _SLOT, layer_of


class ConfigArch:
    """A configuration file as the program's architecture protocol: its
    widths are written out in the file, so no program default moves
    them."""

    def __init__(self, config: dict):
        self._config = config

    def chip_share(self):
        from repro.models.common import ModelConfig, RunConfig

        return ModelConfig(**self._config["model"]), \
            RunConfig(**self._config["run"])

    def config(self):
        return self.chip_share()[0]

    def reduced(self):
        return self.chip_share()


def register(config: dict) -> str:
    from repro.api import register_arch

    name = "bench:" + config["name"]
    register_arch(name, ConfigArch(config), overwrite=True)
    return name


def row_norms(tree, scale: float = 1.0):
    """Norm of each row of each stacked leaf (traceable)."""
    def f(a):
        sq = jnp.square(a.astype(jnp.float32))
        return jnp.sqrt(jnp.sum(sq, tuple(range(1, a.ndim)))) * scale
    return {"io": {n: f(a[None]) for n, a in tree["io"].items()},
            "segments": jax.tree.map(f, tree["segments"])}


def leaf_norms(rows, geo, m: dict) -> dict:
    """``row_norms`` keyed as the reference keys them: ("io", name) ->
    [norm], ("layers", name) -> norm per layer."""
    got = jax.device_get(rows)
    out = {("io", n): np.asarray(v, np.float64)
           for n, v in got["io"].items()}
    segs = {s.name: s for s in geo.segments}
    per_layer: dict[str, np.ndarray] = {}
    for seg_name, leaves in got["segments"].items():
        seg = segs[seg_name]
        for full, vals in leaves.items():
            slot, name = _SLOT.fullmatch(full).groups()
            arr = per_layer.setdefault(name, np.full(m["n_layers"], np.nan))
            for row, v in enumerate(vals):
                layer = layer_of(geo, seg, row, int(slot))
                if layer < m["n_layers"]:
                    arr[layer] = v
    out.update({("layers", n): a for n, a in per_layer.items()})
    return out


def leaf_gaps(prog: dict, ref: dict, keep=None) -> list:
    """Each leaf's (and layer's) gap between the program's norm and the
    reference's, over the larger of that leaf's reference norm and the
    median leaf's: [(gap, key, layer, program norm, reference norm)].
    ``keep`` masks leaves out, keyed like the norms."""
    rows = []
    for key, r in ref.items():
        mask = np.ones(len(r), bool) if keep is None else keep[key]
        rows.extend((key, i, float(prog[key][i]), float(r[i]))
                    for i in np.flatnonzero(mask))
    med = float(np.median([x[3] for x in rows]))
    return sorted(((abs(p - r) / max(r, med), key, i, p, r)
                   for key, i, p, r in rows), reverse=True)


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    """The worst leaf's gap (``leaf_gaps``)."""
    return leaf_gaps(prog, ref, keep)[0][0]
