"""A configuration file read into the sizes the benchmark and its reference use.

The file under ``chipbench/configs/`` is the source of truth for what a cell
runs.  It names its architecture under ``"arch"`` (``dense`` where the key is
absent): the module ``chipbench/archs/<arch>.py``, loaded by its file path
(:func:`load_arch`), reads the file into its sizes and gives the weights, the
plain reference and the FLOP counts of that block.  The program under test has
its own registry of configurations; the harness checks that the program's
entry agrees with the file on every size before it builds anything.

``Dims``, :func:`check_program_config` and :func:`smoke_dims` are the dense
architecture's, kept under the names they had before architectures were found
by name.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

from chipbench.archs import dense
from chipbench.archs.dense import Dims  # noqa: F401

ROOT = Path(__file__).resolve().parent
DEFAULT_ARCH = "dense"


def load_arch(name: str, archs_dir: Path = ROOT / "archs"):
    """The module ``<archs_dir>/<name>.py``, loaded once per process.

    The benchmark's own architectures load as ``chipbench.archs.<name>``; a
    module from another directory (a test's copy) gets a name of its own."""
    path = (Path(archs_dir) / f"{name}.py").resolve()
    key = f"chipbench.archs.{name}"
    if path.parent != ROOT / "archs":
        key = f"{key}@{path.parent}"
    mod = sys.modules.get(key)
    if mod is None:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod  # a dataclass looks its module up while it is made
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
    return mod


def load(name: str, root: Path = ROOT):
    """``(architecture module, dims, raw file)`` of the configuration called
    ``name`` among the files under ``root``; its architecture is looked for
    under ``root`` first, then among the benchmark's own."""
    path = Path(root) / "configs" / f"{name}.json"
    raw = json.loads(path.read_text())
    arch = raw.get("arch", DEFAULT_ARCH)
    archs_dir = Path(root) / "archs"
    if not (archs_dir / f"{arch}.py").exists():  # one of the benchmark's own
        archs_dir = ROOT / "archs"
    arch = load_arch(arch, archs_dir)
    return arch, arch.dims(raw, name), raw


def load_config(name: str, configs_dir: Path = ROOT / "configs") -> tuple:
    """``(dims, raw file)`` of the configuration called ``name``."""
    _, dims, raw = load(name, Path(configs_dir).parent)
    return dims, raw


def check_program_config(dims: Dims, pcfg) -> None:
    """Raise unless the program's ``ModelConfig`` runs exactly these sizes."""
    dense.check_program(dims, pcfg)


def smoke_dims(dims: Dims, pcfg) -> Dims:
    """``dims`` at the program's ``smoke()`` sizes (CPU tests only)."""
    return dense.smoke(dims, pcfg)
