"""Cells of the benchmark cut to a size the CPU runs in seconds, with the
Pallas kernels in interpret mode.  Importing this module puts the
repository root on the path, where the harness is the ``bench`` package;
every test file of this directory imports it before ``bench``."""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = tuple(w["name"] for w in BENCHMARK["workloads"])
_first = {}
for _w in BENCHMARK["workloads"]:
    _first.setdefault(_w["config"], _w["name"])
# the first cell of each configuration, for tests of the data generators
FIRST_CELL_OF_CONFIG = tuple(_first.values())


def _shrink(target: dict, small: dict):
    """Apply a ``small`` block: its numbers replace the target's, and a
    nested block is merged key by key."""
    for key, value in small.items():
        if isinstance(value, dict):
            target[key].update(value)
        else:
            target[key] = value


def small_cell(name: str) -> dict:
    """The cell as ``manifest.cell`` resolves it, with its data set and its
    calls cut down by the ``small`` blocks of its configuration and traffic
    files, and its sample cut down; paths, metrics and limits unchanged."""
    from bench import manifest

    cell = manifest.cell(name)
    _shrink(cell["config"], cell["config"].pop("small"))
    _shrink(cell["traffic"]["queries"], cell["traffic"].pop("small"))
    cell["limits"].update(sample=256, marked_sample=64, low_alpha_sample=64)
    return cell
