"""Finds a cell's files by the names in ``BENCHMARK.json``.

- ``configs/<config>.json``: the models as run, one section each (the
  policy's and the judge's);
- ``arch/<arch>.py``: an architecture, named by a model section's
  ``"arch"`` (a section without one is ``dense``).  It gives ``KEYS``, the
  ``as_run`` keys it reads; ``program_value(cfg, key)``, each key as the
  program's ``ModelConfig`` runs it; ``shapes(dims)``, the parameter tree;
  ``token_logp(params, tokens, dims, prec, remat)``, the plain float32
  forward pass; and the work counts ``forward``, ``decode``,
  ``head_params`` and ``judge_step_bytes``;
- ``traffic/<traffic>.json``: the mix's parameters;
- ``limits/<workload>.json``: the limit of each number the check compares;
- ``metrics/<metric>.py``: one reader per metric, ``read(run) -> float | None``.

A configuration, an architecture, a mix, a cell or a metric is added as
new files plus new entries in ``BENCHMARK.json``; nothing here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
#: the architecture of a model section (or dims) that names none
DEFAULT_ARCH = "dense"
#: architecture modules by name, each loaded once per process
_ARCHES: Dict[str, ModuleType] = {}


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_module(path: Path, prefix: str) -> ModuleType:
    mod_name = prefix + "".join(c if c.isalnum() else "_" for c in path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_reader(path: Path) -> Callable:
    return _load_module(path, "chip_metric_").read


def arch(name: str, arch_dir: Path = HERE / "arch") -> ModuleType:
    """The architecture ``name``: ``<arch_dir>/<name>.py`` the first time
    it is asked for (``load_cell`` asks with the cell's own directory)."""
    if name not in _ARCHES:
        _ARCHES[name] = _load_module(arch_dir / f"{name}.py", "chip_arch_")
    return _ARCHES[name]


def arch_name(section_or_dims: Dict) -> str:
    """The architecture a model section, or the dims made from it, names."""
    return section_or_dims.get("arch", DEFAULT_ARCH)


def arch_of(section_or_dims: Dict) -> ModuleType:
    return arch(arch_name(section_or_dims))


def _metrics(entries: List[Dict], workload: str, bench_dir: Path) -> List[Metric]:
    out = []
    for m in entries:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        out.append(Metric(m["name"], m["unit"], _load_reader(bench_dir / "metrics" / f"{m['name']}.py")))
    return out


def load_cell(workload: str, root: Optional[Path] = None) -> Cell:
    """The cell named ``workload`` of ``<root>/BENCHMARK.json``; its files
    sit beside this module (``root`` defaults to the repository's)."""
    root = Path(root) if root is not None else HERE.parents[1]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench_dir = root / bench["paths"][0]
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    for section in config.values():
        if isinstance(section, dict) and "as_run" in section:
            arch(arch_name(section), bench_dir / "arch")
    traffic = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((bench_dir / "limits" / f"{workload}.json").read_text())
    return Cell(workload, int(w["chips"]), config, traffic, limits,
                _metrics(bench["end_to_end"], workload, bench_dir),
                _metrics(bench["per_layer"], workload, bench_dir))


def dims(section: Dict) -> Dict:
    """A model section's shape as its architecture reads it, and the
    architecture's name under ``"arch"``."""
    a = arch_name(section)
    return {"arch": a, **{k: section["as_run"][k] for k in arch(a).KEYS}}
