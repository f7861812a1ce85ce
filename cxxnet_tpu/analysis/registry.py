"""Declared-key registry: harvest accepted config keys from the code.

Every subsystem that consumes ``name = value`` pairs declares its keys
next to its ``set_param`` (``LAYER_PARAM_KEYS`` / ``extra_config_keys``
in the layers, ``config_keys`` on iterator stages, ``HYPER_KEYS`` in the
updaters, ``TRAINER_KEYS`` / ``TASK_KEYS`` on the trainer and CLI
driver, ``engine.key_specs()`` for the lowering toggles).  This module
assembles those declarations into matchable scopes:

* :func:`global_scope` — keys legal outside any section.  Per the
  reference contract globals are broadcast to every layer, updater, and
  iterator, so this is the union of everything (a key "known anywhere"
  is never a global typo).
* :func:`layer_scope` — keys a ``layer[..] = type`` section accepts:
  the layer type's own keys plus the per-layer updater-hyper overrides.
* :func:`iterator_scope` — keys a ``data``/``eval``/``pred`` section
  accepts for its ``iter =`` stage chain.

Keys whose declared name ends in ``[*]`` are numbered/templated
(``extra_data_shape[0]``, ``metric[field,node]``, ``label_vec[0,4)``)
and match structurally.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, List, Optional, Sequence, Tuple

from .schema import KeySpec

# weight-tag prefixes for tag-scoped hyper overrides (``wmat:lr``,
# ``bias:wd`` — updater/param.h:100-105); the zoo's extra tags included
TAG_PREFIXES = ("wmat", "bias", "gate", "wmat2", "bias2",
                "wqkv", "wout", "bqkv", "wpos", "router", "w13", "w2")

# templated key name -> full-match regex
_TEMPLATES = {
    "extra_data_shape[*]": r"extra_data_shape\[\d+\]",
    "metric[*]": r"metric\[[^\]]+\]",
    "label_vec[*]": r"label_vec\[\d+,\d+\)",
}


class KeyScope:
    """A matchable set of declared keys."""

    def __init__(self, name: str, specs: Sequence[KeySpec]):
        self.name = name
        self._exact: Dict[str, List[KeySpec]] = {}
        self._patterns: List[Tuple[re.Pattern, KeySpec]] = []
        for sp in specs:
            if sp.name.endswith("[*]") or sp.name in _TEMPLATES:
                pat = _TEMPLATES.get(
                    sp.name, re.escape(sp.name[:-3]) + r"\[[^\]]*\]")
                self._patterns.append((re.compile(pat + r"\Z"), sp))
            else:
                self._exact.setdefault(sp.name, []).append(sp)

    def match(self, key: str) -> List[KeySpec]:
        """Specs accepting ``key``, honoring templates and the tag-scoped
        ``wmat:``/``bias:`` prefix spellings.  Empty list = undeclared."""
        got = self._exact.get(key)
        if got:
            return got
        for pat, sp in self._patterns:
            if pat.match(key):
                return [sp]
        head, _, tail = key.partition(":")
        if tail and head in TAG_PREFIXES:
            return self.match(tail)
        return []

    def names(self) -> List[str]:
        """Exact key names (did-you-mean candidates)."""
        return sorted(self._exact)


def _netcfg_keys() -> Tuple[KeySpec, ...]:
    from ..updater.updaters import _UPDATERS
    from .schema import K
    return (
        K("netconfig", "enum", choices=("start", "end")),
        K("updater", "enum", choices=tuple(sorted(_UPDATERS))),
        K("sync", "str"),
        K("input_shape", "str", help="c,y,x"),
        K("extra_data_num", "int", lo=0),
        K("extra_data_shape[*]", "str", help="c,y,x"),
        K("label_vec[*]", "str", help="label field name for columns [a,b)"),
        K("loop[*]", "int", lo=1,
          help="loop[read->write] = T: the layers up to 'loop = end' run T "
               "times, each pass reading at `read` what the last wrote to "
               "`write`"),
        K("loop", "enum", choices=("end",)),
    )


def _all_iterator_keys() -> Tuple[KeySpec, ...]:
    from ..io import factory
    out: List[KeySpec] = []
    seen = set()
    stages = [c for classes in factory.ITER_STAGES.values() for c in classes]
    for cls in stages:
        for sp in getattr(cls, "config_keys", ()):
            if (cls.__name__, sp.name) not in seen:
                seen.add((cls.__name__, sp.name))
                out.append(sp)
    return tuple(out)


def _all_layer_keys() -> Tuple[KeySpec, ...]:
    from ..layers import registry as lreg
    from ..layers.base import LAYER_PARAM_KEYS
    out: List[KeySpec] = list(LAYER_PARAM_KEYS)
    for entry in lreg._REGISTRY.values():
        if isinstance(entry, type):
            for klass in entry.__mro__:
                out.extend(klass.__dict__.get("extra_config_keys", ()))
    return tuple(out)


@functools.lru_cache(maxsize=1)
def global_scope() -> KeyScope:
    from .. import engine
    from ..main import TASK_KEYS
    from ..nnet.trainer import TRAINER_KEYS
    from ..updater.updaters import HYPER_KEYS
    specs = (tuple(TASK_KEYS) + tuple(TRAINER_KEYS) + engine.key_specs()
             + tuple(HYPER_KEYS) + _netcfg_keys() + _all_iterator_keys()
             + _all_layer_keys())
    return KeyScope("global", specs)


@functools.lru_cache(maxsize=64)
def layer_scope(type_name: str) -> Optional[KeyScope]:
    """Scope for one layer section, or None when the type's key surface
    is unknowable here (unresolvable plugin) — the caller then skips key
    lint for that section rather than guessing."""
    from ..layers import registry as lreg
    from ..updater.updaters import HYPER_KEYS
    specs = _layer_type_specs(type_name)
    if specs is None:
        return None
    return KeyScope(f"layer:{type_name}", tuple(specs) + tuple(HYPER_KEYS))


def _layer_type_specs(type_name: str):
    from ..layers import registry as lreg
    if type_name.startswith("pairtest-"):
        rest = type_name[len("pairtest-"):]
        if "-" not in rest:
            return None
        master, slave = rest.split("-", 1)
        m, s = _layer_type_specs(master), _layer_type_specs(slave)
        if m is None or s is None:
            return None
        # master:/slave: routed spellings resolve through the tagless
        # union; PairTestLayer broadcasts untagged keys to both sides
        return list(m) + list(s)
    if type_name == "torch":
        try:
            from ..plugin.torch_adapter import TorchLayer
            return list(TorchLayer.config_keys())
        except Exception:  # noqa: BLE001 — optional plugin
            return None
    entry = lreg._REGISTRY.get(type_name)
    if not isinstance(entry, type):
        return None
    return list(entry.config_keys())


def layer_key_match(type_name: str, key: str) -> List[KeySpec]:
    """Match a layer-section key, honoring pairtest ``master:``/``slave:``
    routing prefixes."""
    scope = layer_scope(type_name)
    if scope is None:
        return []
    head, _, tail = key.partition(":")
    if tail and head in ("master", "slave") \
            and type_name.startswith("pairtest-"):
        return layer_key_match(type_name, tail) or scope.match(key)
    return scope.match(key)


def iterator_scope(chain: Tuple[str, ...]) -> KeyScope:
    from ..io import factory
    specs: List[KeySpec] = []
    for t in chain:
        classes = factory.iter_stage_classes(t)
        for cls in classes or ():
            specs.extend(getattr(cls, "config_keys", ()))
    return KeyScope("iter:" + "+".join(chain), specs)


def known_anywhere(key: str) -> bool:
    return bool(global_scope().match(key))
