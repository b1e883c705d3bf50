"""The files a configuration brings, found by the path its file names.

Everything that depends on the architecture lies behind two keys of the
configuration's file, and the harness takes it from there and from nowhere
else:

- ``reference``: the module with the program's layer list and the weights
  and tokens from the seed (``REFERENCE``), and the plain reference in two
  halves: the comparison of what was served (``SERVING``), asked of it
  only where a cell serves, and the three names that follow training
  (``TRAINING``), asked only where a cell trains. A module may leave either
  half out, not both. The child loads it, so it may import jax at its top.
- ``counts``: the module with the operations and bytes (``COUNTS``). The
  parent loads it and never imports jax, so neither may this module: it is
  plain arithmetic on the configuration's sizes.

No jax here.
"""

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: what ``work.py`` asks the ``counts`` module for, each ``f(cfg, ...)``
COUNTS = ("dims", "matmul_params", "model_params", "attention_flops_forward",
          "kv_bytes_per_token")
#: what every ``reference`` module exports
REFERENCE = ("layer_list", "make_weights", "make_tokens")
#: what a ``serve`` cell needs besides, and what a ``train`` cell does: the
#: two halves of the plain reference, of which a module may leave one out
SERVING = ("served_gaps",)
TRAINING = ("train_reference", "delta_norms", "leaf_norms")


class ContractError(Exception):
    """A configuration names no module, or its module lacks a name or a
    half that the cell needs."""


def load_file(path):
    """The module at ``path`` (relative to the checkout's root), loaded
    once. A file of the ``chipbench`` package is that package's module, so
    ``from chipbench import reference`` gives the same object."""
    full = os.path.normpath(os.path.join(ROOT, path))
    rel = os.path.relpath(full, ROOT)
    if rel.startswith("..") or not rel.endswith(".py"):
        raise ContractError("%r is no Python file of this checkout" % path)
    name = rel[:-3].replace(os.sep, ".")
    if name in sys.modules:
        return sys.modules[name]
    if not os.path.exists(full):
        raise ContractError("no file %s" % rel)
    spec = importlib.util.spec_from_file_location(name, full)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def _lacks(mod, names):
    return [n for n in names if not callable(getattr(mod, n, None))]


def _module(cfg, key, names, half=""):
    """The module that ``cfg[key]`` names, held to ``names``; ``half`` ends
    the error's line where the names are one half of the reference."""
    path = cfg.get(key)
    if not path:
        raise ContractError("configuration %s names no %r file"
                            % (cfg.get("name"), key))
    mod = load_file(path)
    missing = _lacks(mod, names)
    if missing:
        raise ContractError("%s (the %r of configuration %s) exports no %s%s"
                            % (path, key, cfg.get("name"),
                               ", ".join(missing), half))
    return mod


def reference_of(cfg, training=False, serving=False):
    """The configuration's ``reference`` module, held to ``REFERENCE``; for
    a cell that trains also to ``TRAINING``, for one that serves also to
    ``SERVING``. Asked for neither half, it still has to hold one."""
    mod = _module(cfg, "reference", REFERENCE)
    if training:
        _module(cfg, "reference", TRAINING,
                ": the training half (TRAINING), which a train cell needs")
    if serving:
        _module(cfg, "reference", SERVING,
                ": the serving half (SERVING), which a serve cell needs")
    if _lacks(mod, TRAINING) and _lacks(mod, SERVING):
        raise ContractError(
            "%s (the 'reference' of configuration %s) exports neither half "
            "of the plain reference: the serving one (SERVING) lacks %s, the "
            "training one (TRAINING) lacks %s"
            % (cfg["reference"], cfg.get("name"),
               ", ".join(_lacks(mod, SERVING)),
               ", ".join(_lacks(mod, TRAINING))))
    return mod


def counts_of(cfg):
    """The configuration's ``counts`` module, held to ``COUNTS``."""
    return _module(cfg, "counts", COUNTS)
