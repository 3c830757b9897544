"""A family of architectures is a directory ``benchmark/families/<name>/`` of
three files, found by the name a configuration's file gives under ``family``:

``modelfile.py``
    ``model_spec(config, seq_len)`` (the program's ``ModelSpec``: the file's
    header and tensor order), ``role(name)`` (which of the shared drawing
    rules of ``harness/modelfile.py`` a tensor falls under, or None) and,
    where some tensor has no role, ``draw(entry, rng)``.
``reference.py``
    ``header(raw)`` (the ``.m`` header's numbered values -> named ones, with
    the family's own checks), ``layout(h)`` (the tensors in file order) and
    ``forward(qf, tokens, positions, router_gaps)``, the plain float32
    forward pass. Imports nothing of the program.
``counts.py``
    ``CONFIG_KEYS`` (every configuration key the family's functions read, or
    knowingly leave alone), ``decode_step_bytes(config, rows,
    live_positions)`` and ``kernel_launch(config, role, shape)`` (the floor
    of bytes and the operations of one launch of a named kernel).

This module is the one lookup; harness, reference child, rehearsal and tools
all come through it, and none of them names a family. A part is loaded from
its file's path, so a checkout laid out elsewhere (the tests' miniature)
brings its own families without shadowing a module of this one.
"""

from __future__ import annotations

import importlib.util
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARTS = ("modelfile", "reference", "counts")
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
# keys of a configuration's file that are the harness's own, whatever the family
HARNESS_KEYS = frozenset({"name", "source", "family", "reduced", "reduced_from", "assumed",
                          "deployment", "check", "tokenizer_vocab", "max_position_embeddings"})
_loaded: dict[str, object] = {}


class FamilyError(ValueError):
    """A configuration names no family, one that is not there, or keys its
    family does not know. The message names what is missing."""


def family_of(config: dict) -> str:
    name = config.get("family")
    if not isinstance(name, str) or not _NAME.match(name):
        raise FamilyError(f"configuration {config.get('name')!r} names no family: its file needs a "
                          f"\"family\" key, the name of a directory under benchmark/families/")
    return name


def load(config: dict, part: str, bench_dir: str = BENCH_DIR):
    """The module ``<bench_dir>/families/<config's family>/<part>.py``."""
    if part not in PARTS:
        raise ValueError(f"a family has the parts {PARTS}, not {part!r}")
    family = family_of(config)
    path = os.path.join(os.path.abspath(bench_dir), "families", family, f"{part}.py")
    if path not in _loaded:
        if not os.path.isfile(path):
            raise FamilyError(f"unknown family {family!r} (configuration {config.get('name')!r}): "
                              f"no file {path}")
        name = f"benchmark_family_{len(_loaded)}_{re.sub(r'[^A-Za-z0-9_]', '_', family)}_{part}"
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module  # dataclasses and jit look their module up by name
        spec.loader.exec_module(module)
        _loaded[path] = module
    return _loaded[path]


def counts(config: dict, bench_dir: str = BENCH_DIR):
    """The family's ``counts`` part, after checking that it knows every key
    of ``config``: a key it has never heard of (experts spelled another way,
    a window, a second kind of layer) would otherwise be counted as absent."""
    module = load(config, "counts", bench_dir)
    unknown = sorted(set(config) - HARNESS_KEYS - set(module.CONFIG_KEYS))
    if unknown:
        raise FamilyError(f"configuration {config.get('name')!r} has keys {unknown} that the counts of "
                          f"family {family_of(config)!r} do not know")
    return module
