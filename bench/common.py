"""What every cell shares: the files a cell is made of, its data from the
seed, the compulsory bytes of a step, the peaks table and the comparison
that decides ``correct``.

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration and a traffic mix. Each is a file of its own, found by name:
``configs/<config>.json`` beside its plain reference ``configs/<config>.py``,
``traffic/<traffic>.json``, and the limits of the numbers its check
compares, ``limits/<workload>.json``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: Path, name: str):
    """Import a Python file that is not on the path (a reference or a
    per-layer metric's reader) under ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload: its entry, configuration, traffic and limits."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    reference: object           # the configuration's plain reference module


def load_cell(workload: str) -> Cell:
    """The cell named ``workload`` in ``BENCHMARK.json``; KeyError if none."""
    bench = load_json(ROOT / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}[workload]
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = load_json(ROOT / conf["file"])
    ref_path = (ROOT / conf["file"]).with_suffix(".py")
    return Cell(name=workload, chips=int(entry["chips"]), config=config,
                traffic=load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
                limits=load_json(BENCH / "limits" / f"{workload}.json"),
                reference=load_module(ref_path, f"reference_{conf['name']}"))


# --------------------------------------------------------------------------
# data from the seed
# --------------------------------------------------------------------------

def _field(spec: dict, x):
    """One input field from standard normals ``x``: optionally a 0/1 mask
    of ``x > above`` or ``|x|``, then ``scale * x + offset``."""
    if "above" in spec:
        x = (x > spec["above"]).astype(x.dtype)
    if spec.get("abs"):
        x = jnp.abs(x)
    return spec.get("scale", 1.0) * x + spec.get("offset", 0.0)


def make_fields(config: dict, grid, seed: int, members: int = 0,
                sharding=None) -> dict:
    """The configuration's input fields on ``grid`` from ``seed``, made on
    the device in one jitted call. ``members > 0`` makes that many
    independent states, stacked on a leading axis."""
    names = list(config["fields"])
    shape = ((members,) if members else ()) + tuple(grid)

    def gen(key):
        keys = jax.random.split(key, len(names))
        return {f: _field(config["fields"][f],
                          jax.random.normal(k, shape, jnp.float32))
                for k, f in zip(keys, names)}
    return jax.jit(gen, out_shardings=sharding)(seed_key(seed))


def seed_key(seed: int):
    """A PRNG key from any whole number the driver passes (larger than 32
    bits hold): the seed is folded in as two 32-bit words."""
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.key(seed >> 32), seed & 0xFFFFFFFF)


def make_scalars(config: dict) -> dict:
    return {s: jnp.float32(v) for s, v in config["scalars"].items()}


def make_coeffs(config: dict, grid) -> dict:
    """Per-level coefficients: ``linspace`` between two ends, or a
    constant ``full`` value, along the coefficient's axis."""
    out = {}
    for c, spec in config["coeffs"].items():
        n = int(grid[spec["axis"]])
        if "linspace" in spec:
            lo, hi = spec["linspace"]
            out[c] = jnp.linspace(lo, hi, n, dtype=jnp.float32)
        else:
            out[c] = jnp.full((n,), spec["full"], jnp.float32)
    return out


def compulsory_bytes_per_point_step(config: dict) -> int:
    """Bytes one grid-point update has to move at the least: every input
    field read once and every field the update changes written once, in
    the configuration's dtype. Taken from the configuration, never from a
    plan, so it prices the same work whatever schedule implements it."""
    width = jnp.dtype(config["dtype"]).itemsize
    return width * (len(config["fields"]) + len(config["updated"]))


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device missing from
    ``peaks.json`` is an error, not a default."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]


# --------------------------------------------------------------------------
# the comparison that decides ``correct``
# --------------------------------------------------------------------------

def _worse(a: float, b: float) -> float:
    """The larger of two readings, NaN counting as the largest."""
    return a if (a != a or a >= b) else b


def compare(config: dict, start: dict, got: dict, ref: dict) -> dict:
    """The numbers one answer is judged by, each against the reference run
    from the same ``start``:

    * ``change_err``: over the fields the update changes, the largest
      ``max|got - ref| / max|ref - start|``, the error as a share of the
      change the reference made. An answer that leaves its state
      unchanged reads 1; a NaN or infinity in it reads NaN or infinity.
    * ``steady_err``: over the fields the update leaves alone, the largest
      ``max|got - start|``; they are carried, never computed, so exactly 0.
    """
    change, steady = 0.0, 0.0
    for f in config["fields"]:
        g = jnp.asarray(got[f], jnp.float32)
        s = jnp.asarray(start[f], jnp.float32)
        if f in config["updated"]:
            r = jnp.asarray(ref[f], jnp.float32)
            den = float(jnp.max(jnp.abs(r - s)))
            num = float(jnp.max(jnp.abs(g - r)))
            change = _worse(change, num / den if den > 0 else math.inf)
        else:
            steady = _worse(steady, float(jnp.max(jnp.abs(g - s))))
    out = {"change_err": change}
    if len(config["updated"]) < len(config["fields"]):
        out["steady_err"] = steady
    return out


def worst(readings: list) -> dict:
    """Per number, the largest reading over several answers."""
    out: dict = {}
    for r in readings:
        for k, v in r.items():
            out[k] = _worse(out.get(k, v), v)
    return out


def judge(readings: dict, limits: dict) -> tuple:
    """``(correct, lines)``: every number at or under its limit, and one
    ``{"value", "limit"}`` entry per number."""
    lines = {k: {"value": readings.get(k, float("inf")), "limit": lim}
             for k, lim in limits.items()}
    ok = bool(readings) and all(e["value"] <= e["limit"]
                                for e in lines.values())
    return ok, lines


def plain(x):
    """``x`` with every NaN or infinity written as a string, so that the
    result line stays strict JSON."""
    if isinstance(x, dict):
        return {k: plain(v) for k, v in x.items()}
    if isinstance(x, list):
        return [plain(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return repr(x)
    return x
