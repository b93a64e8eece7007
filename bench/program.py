"""The benchmark's side of the program's interface: registering a
configuration file as an architecture, building the session a cell
asks for, and reading per-leaf norms out of the program's trees."""

from __future__ import annotations

import dataclasses
import typing

import jax
import jax.numpy as jnp
import numpy as np

from model import _SLOT, layer_of, layer_tables


class ConfigArch:
    """A configuration file as the program's architecture protocol: its
    widths are written out in the file, so no program default moves
    them."""

    def __init__(self, config: dict):
        self._config = config

    def chip_share(self):
        from repro.models.common import ModelConfig, RunConfig

        return _dataclass(ModelConfig, self._config["model"]), \
            _dataclass(RunConfig, self._config["run"])

    def config(self):
        return self.chip_share()[0]

    def reduced(self):
        return self.chip_share()


def _dataclass(cls, values: dict):
    """``cls(**values)``, each group given as a dict (a "moe" block, say)
    made into the dataclass that its field's type names."""
    hints = typing.get_type_hints(cls)
    out = {}
    for k, v in values.items():
        if isinstance(v, dict):
            kind = next(t for t in typing.get_args(hints[k]) or (hints[k],)
                        if dataclasses.is_dataclass(t))
            v = kind(**v)
        out[k] = v
    return cls(**out)


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


def leaf_norms(rows, geo, arch, m: dict) -> dict:
    """``row_norms`` keyed as the reference keys them: ("io", name) ->
    [norm], ("layers", name) -> norm of each layer that holds the leaf,
    in layer order."""
    got = jax.device_get(rows)
    out = {("io", n): np.asarray(v, np.float64)
           for n, v in got["io"].items()}
    holds: dict[str, list] = {}
    for layer, table in enumerate(layer_tables(arch, m)):
        for name in table:
            holds.setdefault(name, []).append(layer)
    per_layer = {n: np.full(len(ls), np.nan) for n, ls in holds.items()}
    segs = {s.name: s for s in geo.segments}
    for seg_name, leaves in got["segments"].items():
        seg = segs[seg_name]
        for full, vals in leaves.items():
            slot, name = _SLOT.fullmatch(full).groups()
            for row, v in enumerate(vals):
                layer = layer_of(geo, seg, row, int(slot))
                if layer < m["n_layers"]:
                    per_layer[name][holds[name].index(layer)] = v
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
