"""The one traffic generator: request bodies from a configuration, a mix
and a seed.

A configuration is ``configs/<name>.json`` (its sizes) with
``configs/<name>.py`` beside it, whose ``state(rng, label, cfg)`` draws
one cluster state in the service's wire form.  A mix is
``traffic/<name>.json``:

- ``clients``: closed-loop callers, each sending its next request when
  the last one is answered;
- ``states_per_request``: more than one makes a ``{"problems": [...]}``
  body, one a single ``{"variables": [...]}`` document;
- ``repeat_share``: the share of the result cache's lookups that may
  hit; every state drawn is new, so the mixes here set 0;
- ``span_draws`` (optional): warm-up first sends up to ``PER_CLASS``
  states of each size class found among this many draws of a stream
  that is the same for every seed (see :meth:`Traffic.span`).

Request ``k`` of a stream is drawn from ``(seed, stream, k)`` alone, so a
request can be rebuilt after the fact, and streams with different names
never share a state.  Nothing here imports JAX or the program.
"""

from __future__ import annotations

import importlib.util
import json
import os
import random
from collections import Counter
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
PER_CLASS = 8   # span states kept per size class


def pow2(n: int) -> int:
    out = 1
    while out < n:
        out <<= 1
    return out


def size_class(doc: dict) -> tuple:
    """Powers of two at or above a wire-form problem's sizes: variables,
    constraints, dependencies on one variable, a variable's occurrences,
    and each constraint kind's count and widest list.  A solver that pads
    its batches to powers of two compiles one program per class at
    most."""
    kinds: Counter = Counter()
    widest: Counter = Counter()
    occurs: Counter = Counter()
    per_var = 0
    for v in doc["variables"]:
        deps = 0
        for con in v.get("constraints", []):
            kind = con["type"]
            ids = con.get("ids") or ([con["id"]] if "id" in con else [])
            kinds[kind] += 1
            widest[kind] = max(widest[kind], len(set(ids)))
            deps += kind == "dependency"
            occurs[v["id"]] += 1
            occurs.update(ids)
        per_var = max(per_var, deps)
    sizes = [len(doc["variables"]), sum(kinds.values()), per_var,
             max(occurs.values(), default=0)]
    sizes += [kinds[k] for k in sorted(kinds)]
    sizes += [widest[k] for k in sorted(widest)]
    return tuple(pow2(n) for n in sizes)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json``, with its files loaded."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    mix: dict

    @classmethod
    def load(cls, bench: dict, name: str) -> "Cell":
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        w = cells[name]
        return cls(name=name, chips=int(w["chips"]),
                   config_name=w["config"],
                   config=load_json(os.path.join(HERE, "configs",
                                                 w["config"] + ".json")),
                   traffic_name=w["traffic"],
                   mix=load_json(os.path.join(HERE, "traffic",
                                              w["traffic"] + ".json")))


class Traffic:
    """Request bodies for one cell and seed."""

    def __init__(self, config_name: str, config: dict, mix: dict, seed: int):
        self.family = load_module(
            os.path.join(HERE, "configs", config_name + ".py"),
            "chipbench_config_" + config_name.replace("-", "_"))
        self.config = config
        self.mix = mix
        self.seed = int(seed)
        self.per_request = int(mix["states_per_request"])

    def _rng(self, *parts) -> random.Random:
        return random.Random("/".join(str(p) for p in (self.seed,) + parts))

    def states(self, stream: str, k: int, n: int = 0) -> list:
        """The states of request ``k`` of ``stream`` (``n`` of them, or the
        mix's number)."""
        rng = self._rng(stream, k)
        return [self.family.state(rng, f"{stream}{k}.{i}", self.config)
                for i in range(n or self.per_request)]

    def body(self, stream: str, k: int, n: int = 0) -> bytes:
        return encode(self.states(stream, k, n))

    def span(self) -> list:
        """Up to ``PER_CLASS`` states of each size class among the mix's
        ``span_draws`` draws of the ``span`` stream, which does not depend
        on the seed: every run warms the same shapes, the rare ones
        included, so that none compiles in the window."""
        kept: dict = {}
        for k in range(int(self.mix.get("span_draws", 0))):
            state = self.family.state(random.Random(f"span/{k}"), f"span{k}",
                                      self.config)
            group = kept.setdefault(size_class(state), [])
            if len(group) < PER_CLASS:
                group.append(state)
        return [s for group in kept.values() for s in group]


def encode(states: list) -> bytes:
    """A request body: ``{"problems": [...]}`` for several states, the
    state itself for one."""
    doc = {"problems": states} if len(states) > 1 else states[0]
    return json.dumps(doc, separators=(",", ":")).encode()
