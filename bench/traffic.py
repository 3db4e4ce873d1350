"""The general request generator: one traffic mix file, one seed, one plan.

A mix is a JSON file ``bench/traffic/<name>.json``. Its keys:

* ``kind``: ``"closed"``, a saturating closed loop: the next request is
  sent as soon as the previous call returns;
* ``popularity``: ``"rounds"`` (every sensor once per round, the order
  shuffled) or ``"zipf"`` with exponent ``zipf_s`` (sensor ranks
  shuffled from the seed, then a fixed deck of draws per ``deck`` requests);
* ``policies``: counts per deck of policies, e.g. ``{"bt": 4,
  "lossless": 1}``; each deck is shuffled, so every seed sends the same
  mix in another order. A key is a policy, sent over the configuration's
  transport, or ``"<policy>/<transport>"``, e.g. ``"lossless/block8"``:
  exact fusion in the service, int8 on the wire;
* ``signals_per_sensor``: size of the pool of signals drawn per sensor.
  Each sensor takes its signals in turn, the warm-up (stream 1) from the
  pool's second half, so a signal comes back only after the whole pool:
  make the pool larger than a window's requests per sensor, since a
  repeated signal repeats its answer and whatever the service caches
  for it (the SE-drift prediction of its realized BT schedule);
* ``compare``: how many answers of each key are compared with the
  reference after the window, drawn from the seed.

A new mix is a new file: nothing here names a mix.
"""
from __future__ import annotations

import json
import os

import numpy as np

KINDS = ("closed",)
POLICIES = ("lossless", "bt", "dp")
TRANSPORTS = ("ecsq", "block8", "block4")
DECK = 64   # draws per popularity deck


def load(root: str, name: str) -> dict:
    """The mix ``name`` from ``<root>/bench/traffic/<name>.json``."""
    path = os.path.join(root, "bench", "traffic", f"{name}.json")
    with open(path) as fh:
        mix = json.load(fh)
    if mix.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind must be one of {KINDS}")
    if not mix.get("policies"):
        raise ValueError(f"{path}: policies must be counts of {POLICIES}")
    for key in (*mix["policies"], *mix.get("compare", {})):
        policy, sep, transport = key.partition("/")
        if policy not in POLICIES or (sep and transport not in TRANSPORTS):
            raise ValueError(f"{path}: {key!r} is not a policy of "
                             f"{POLICIES}, alone or as '<policy>/"
                             f"<transport>' with a transport of "
                             f"{TRANSPORTS}")
    return mix


def split_key(key: str, transport: str) -> tuple:
    """(policy, transport) of a mix key; a bare policy goes over
    ``transport``, the configuration's."""
    policy, _, own = key.partition("/")
    return policy, own or transport


class Plan:
    """Endless, deterministic request plan: ``next()`` gives (sensor,
    policy, signal index). Two plans of one (seed, stream) are equal;
    streams separate warm-up from window."""

    def __init__(self, mix: dict, sensors: int, seed: int, stream: int):
        self.mix = mix
        self.sensors = sensors
        self.rng = np.random.default_rng([int(seed) % (1 << 64), stream])
        self._deck_pol: list = []
        self._deck_sensor: list = []
        self._uses = np.full(sensors, stream * (mix["signals_per_sensor"] // 2),
                             np.int64)
        if mix["popularity"] == "zipf":
            ranks = self.rng.permutation(sensors)
            w = 1.0 / (1.0 + np.arange(sensors)) ** float(mix["zipf_s"])
            # a fixed deck: each sensor's share of DECK draws by its weight
            counts = np.floor(w / w.sum() * DECK + 0.5).astype(int)
            counts[0] += DECK - counts.sum()
            self._zipf_deck = np.repeat(ranks, counts)
        elif mix["popularity"] != "rounds":
            raise ValueError(f"popularity {mix['popularity']!r}")

    def _sensor(self) -> int:
        if not self._deck_sensor:
            if self.mix["popularity"] == "rounds":
                self._deck_sensor = list(self.rng.permutation(self.sensors))
            else:
                self._deck_sensor = list(self.rng.permutation(
                    self._zipf_deck))
        return int(self._deck_sensor.pop())

    def _policy(self) -> str:
        if not self._deck_pol:
            deck = [p for p, c in sorted(self.mix["policies"].items())
                    for _ in range(int(c))]
            self._deck_pol = [deck[i] for i in self.rng.permutation(len(deck))]
        return self._deck_pol.pop()

    def next(self) -> tuple:
        s = self._sensor()
        pol = self._policy()
        k = int(self._uses[s] % self.mix["signals_per_sensor"])
        self._uses[s] += 1
        return s, pol, k

    def take(self, count: int) -> list:
        return [self.next() for _ in range(count)]
