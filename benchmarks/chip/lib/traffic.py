"""The one general traffic generator: from a cell's data file, its payloads'
data files and the seed to the turns that the clients send.

Two orders exist. `deck`: stateless turns dealt from a deck that holds the
mix in exact proportion, evenly interleaved and begun at a seeded place; the
clients draw from one shared sequence. `sessions`: sessions of dependent
turns, one after the other, each of one of the mix's variants in a seeded
order. The seed changes the order and the drawn values, never the amount of
work: every seed sends the same deck and the same variants.
"""

from __future__ import annotations

import hashlib
import itertools
import json
import math
import random


def evaluate(expr, params: dict):
    """A number, or an arithmetic expression of the parameters' names."""
    if isinstance(expr, (int, float)):
        return expr
    return eval(expr, {"__builtins__": {}}, dict(params))  # noqa: S307 — the benchmark's own data files


def deal(mix: dict[str, int]) -> list[str]:
    """The smallest deck that holds `mix` in exact proportion, each payload's
    cards spread evenly over it."""
    divisor = math.gcd(*mix.values())
    counts = {name: weight // divisor for name, weight in mix.items()}
    size = sum(counts.values())
    cards = [
        ((j + 0.5) * size / count, order, name)
        for order, (name, count) in enumerate(sorted(counts.items(), key=lambda kv: (-kv[1], kv[0])))
        for j in range(count)
    ]
    return [name for _, _, name in sorted(cards)]


def seeded_bytes(salt: str, name: str, size: int) -> bytes:
    return random.Random(f"{salt}/{name}").randbytes(size)


class Plan:
    """What one run sends. `payloads` is {name: spec as Manifest.payload
    gives it}; `rehearse` takes each payload's tiny sizes; `control` sends
    each array payload's lower-precision variant while the reference keeps
    the sound source."""

    def __init__(self, traffic: dict, payloads: dict, seed: int, *,
                 rehearse: bool = False, control: bool = False, trace: bool = False) -> None:
        self.traffic, self.payloads, self.seed = traffic, payloads, seed
        self.rehearse, self.control, self.trace = rehearse, control, trace
        self._blobs: dict[str, bytes] = {}
        self.clients = traffic["clients"]
        self.order = traffic["order"]
        self.profile_every = traffic.get("trace", {}).get("profile_every", 0) if trace else 0
        if self.order == "deck":
            self.deck = deal(traffic["mix"])
            self.start = random.Random(f"{seed}/start").randrange(len(self.deck))
        elif self.order == "sessions":
            self.session = traffic["session"]
        else:
            raise ValueError(f"unknown order {self.order!r}")

    # -- one turn
    def turn(self, name: str, params: dict, chain: str, place: int, profile: bool) -> dict:
        spec = self.payloads[name]
        sound = dict(spec["params"], **(spec.get("rehearse", {}) if self.rehearse else {}), **params)
        sent = dict(sound, **spec.get("control", {})) if self.control else sound
        inputs, input_keys = {}, {}
        for item in spec.get("inputs", []):
            if any(sound.get(k) != v for k, v in item.get("when", {}).items()):
                continue
            if "text" in item:  # a file whose content the source states
                data = item["text"].encode()
                key = f"text/{item['name']}/{hashlib.sha256(data).hexdigest()}"
                self._blobs[key] = data
                inputs[item["name"]], input_keys[item["name"]] = data, key
                continue
            salt = str(self.seed) if item["salt"] == "seed" else f"{item['salt']}={sound[item['salt']]}"
            size = int(evaluate(item["bytes"], sound))
            for i in range(int(evaluate(item.get("count", 1), sound))):
                file_name = item["name"].format(i=i)
                key = f"{salt}/{file_name}/{size}"
                if key not in self._blobs:
                    self._blobs[key] = seeded_bytes(salt, file_name, size)
                inputs[file_name], input_keys[file_name] = self._blobs[key], key
        return {
            "payload": name,
            "params": sound,
            "source": f"P = {sent!r}\n{spec['text']}",
            "reference_source": f"P = {sound!r}\n{spec['text']}",
            "inputs": inputs,
            "input_keys": input_keys,
            "chain": chain,
            "place": place,
            "profile": profile,
        }

    def _drawn(self, name: str, index: int) -> dict:
        rng = random.Random(f"{self.seed}/{index}/{name}")
        return {k: rng.choice(v) for k, v in sorted(self.payloads[name].get("draw", {}).items())}

    def _profiled(self, name: str, nth: int) -> bool:
        """Every `profile_every`-th turn of a payload that states a floor:
        the others run no device program and their traces hold no device
        plane."""
        return bool(self.profile_every) and "floor" in self.payloads[name] and nth % self.profile_every == 0

    # -- deck order
    def stateless(self, index: int) -> dict:
        """The index-th turn of the shared sequence."""
        place = (self.start + index) % len(self.deck)
        name = self.deck[place]
        nth = sum(1 for i in range(index) if self.deck[(self.start + i) % len(self.deck)] == name)
        params = self._drawn(name, index)
        chain = f"{name}:{json.dumps(params, sort_keys=True)}"
        return self.turn(name, params, chain, 0, self._profiled(name, nth))

    # -- sessions order
    def session_turns(self, n: int) -> tuple[str, list[dict]]:
        """(executor id, the turns) of the n-th session. Variants come in
        blocks that hold each once, in a seeded order."""
        variants = self.session["variants"]
        block = random.Random(f"{self.seed}/block/{n // len(variants)}").sample(
            range(len(variants)), len(variants))
        v = block[n % len(variants)]
        name, count = self.session["payload"], self.session["turns"]
        turns = [
            self.turn(name, dict(variants[v], T=t + 1), f"session:{v}", t,
                       self._profiled(name, n * count + t))
            for t in range(count)
        ]
        tag = hashlib.sha256(f"{self.seed}/{n}".encode()).hexdigest()[:12]
        return f"bench-{tag}", turns

    # -- what set-up sends once
    def warmup(self) -> list[tuple[str | None, list[dict]]]:
        """Every distinct turn of the cell once, as (executor id or None,
        turns): each payload of the deck, one that states a floor with each
        value its draw can take (a drawn python scalar is part of the
        compiled program), or one whole session of each variant; in a traced run one profiled turn of
        each payload that has them as well."""
        groups: list[tuple[str | None, list[dict]]] = []
        if self.order == "deck":
            for name in sorted(set(self.deck)):
                spec = self.payloads[name]
                draw = spec.get("draw", {}) if "floor" in spec else {}
                turn = None
                for values in itertools.product(*(draw[k] for k in sorted(draw))):
                    params = dict(self._drawn(name, 0), **dict(zip(sorted(draw), values)))
                    turn = self.turn(name, params, f"warmup:{name}", 0, False)
                    groups.append((None, [turn]))
                if self._profiled(name, 0):
                    groups.append((None, [dict(turn, profile=True)]))
        else:
            for n in range(len(self.session["variants"])):
                turns = [dict(t, profile=False) for t in self.session_turns(n)[1]]
                if self.profile_every and n == 0:
                    turns[-1]["profile"] = True
                groups.append((f"bench-warmup-{n}", turns))
        return groups
