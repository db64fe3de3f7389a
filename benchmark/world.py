"""The seeded world and the one traffic generator every mix runs through.

A configuration file (``benchmark/configs/<name>.json``) fixes the
deployment: the ``[aoi]`` keys a studio writes, how many entities are
active, in how many spaces, at which AOI radius, on how wide a world.
A traffic file (``benchmark/traffic/<name>.json``) is parameters only:

- ``move_share``: share of active entities that take a sync step per tick
  (uniform ``±step`` per axis, clipped to the world; the reference bot's
  100 ms random walk, ClientBot.go:225-237, takes it for every avatar);
- ``despawn_share``: share of live entities that leave per tick, and as
  many spawn into slots that have been free for ``free_ticks`` ticks or
  more, at uniform random points;
- ``teleport_share``: share of live entities moved to uniform random
  points per tick.

Every seed gives the same sizes and the same counts per tick; the seed
only picks who moves and where. The program sees nothing but the epoch
arrays ``(pos, active, space, radius)`` and whether ``active``, ``space``
or ``radius`` changed since the previous tick.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

TRAFFIC_KEYS = ("move_share", "step", "despawn_share", "free_ticks",
                "teleport_share")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_traffic(name: str, root: str = HERE) -> dict:
    """``<root>/traffic/<name>.json``, checked for the generator's keys."""
    t = load_json(os.path.join(root, "traffic", f"{name}.json"))
    missing = [k for k in TRAFFIC_KEYS if k not in t]
    if missing:
        raise ValueError(f"traffic {name!r} lacks {missing}")
    return t


@dataclass
class Epoch:
    """One tick's inputs. ``meta_dirty``: active, space or radius differ
    from the previous epoch."""

    pos: np.ndarray  # f32[capacity, 2]
    active: np.ndarray  # bool[capacity]
    space: np.ndarray  # i32[capacity]
    radius: np.ndarray  # f32[capacity]
    meta_dirty: bool

    def arrays(self) -> tuple:
        return self.pos, self.active, self.space, self.radius


class World:
    """The configuration's world at ``capacity`` slots, driven by one
    traffic mix. ``epoch()`` is the current tick's inputs; ``advance()``
    makes the next tick's as new arrays (old epochs stay valid, so the
    reference can read them after the window)."""

    SPACE_ID = 1  # game space ids start at 1

    def __init__(self, config: dict, traffic: dict, capacity: int,
                 seed: int) -> None:
        n = int(config["entities"])
        if n > capacity:
            raise ValueError(f"{n} entities exceed {capacity} slots")
        self.rng = np.random.default_rng(seed)
        self.extent = float(config["world_extent"])
        # Clip bound: strictly inside the world, so a clipped entity never
        # lands on the next (wrapped) grid column.
        self.hi = np.float32(self.extent * (1 - 1e-6))
        self.traffic = traffic
        self.capacity = capacity
        self.n_live = n
        spaces = int(config["spaces"])
        active = np.zeros(capacity, bool)
        active[:n] = True
        # Every slot has its space, so an entity that spawns into a free
        # slot joins the world's spaces.
        space = (self.SPACE_ID + np.arange(capacity) % spaces).astype(
            np.int32)
        radius = np.full(capacity, np.float32(config["aoi_radius"]),
                         np.float32)
        pos = self._uniform(capacity)
        self.tick = 0
        # Tick at which each slot last went free (never-used slots: long ago).
        self.freed_at = np.full(capacity, -(1 << 30), np.int64)
        self.cur = Epoch(pos, active, space, radius, True)

    def _uniform(self, k: int) -> np.ndarray:
        return np.minimum(self.rng.random((k, 2), np.float32)
                          * np.float32(self.extent), self.hi)

    def _pick(self, pool: np.ndarray, share: float) -> np.ndarray:
        k = int(round(share * self.n_live))
        if k > len(pool):
            raise ValueError(f"traffic wants {k} of a pool of {len(pool)}")
        return self.rng.choice(pool, size=k, replace=False) if k else pool[:0]

    def epoch(self) -> Epoch:
        return self.cur

    def advance(self) -> Epoch:
        t = self.traffic
        prev = self.cur
        self.tick += 1
        pos = prev.pos.copy()
        active = prev.active
        live = np.flatnonzero(active)
        movers = self._pick(live, float(t["move_share"]))
        step = np.float32(t["step"])
        pos[movers] += self.rng.uniform(-step, step, (len(movers), 2)).astype(
            np.float32)
        np.clip(pos, 0, self.hi, out=pos)
        dirty = False
        if t["despawn_share"]:
            active = active.copy()
            gone = self._pick(live, float(t["despawn_share"]))
            free = np.flatnonzero(
                ~active & (self.freed_at <= self.tick - int(t["free_ticks"])))
            born = self._pick(free, float(t["despawn_share"]))
            active[gone] = False
            self.freed_at[gone] = self.tick
            active[born] = True
            pos[born] = self._uniform(len(born))
            dirty = True
        if t["teleport_share"]:
            jumpers = self._pick(np.flatnonzero(active),
                                 float(t["teleport_share"]))
            pos[jumpers] = self._uniform(len(jumpers))
        self.cur = Epoch(pos, active, prev.space, prev.radius, dirty)
        return self.cur
