"""Synthetic POI check-in data for the benchmark's configurations.

A copy of the program's generator (`repro.data.synthetic_poi.generate`,
same draws in the same order, so the same seed gives the same data), kept
here so that a change to the program cannot move the benchmark's inputs.
Users and POIs cluster in Zipf(0.8)-sized cities; check-ins are power-law
per user and mostly in the home city; duplicates are dropped, so fewer
unique pairs are realized than the configured ``n_ratings``.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class DataConfig:
    n_users: int
    n_items: int
    n_ratings: int
    n_cities: int
    idiosyncrasy: float = 0.9
    latent_dim: int = 8
    cross_city_frac: float = 0.03
    taste_spatial_scale: float = 0.35
    distance_weight: float = 1.0
    popularity_scale: float = 0.8
    test_frac: float = 0.10
    seed: int = 0


@dataclasses.dataclass
class Dataset:
    config: DataConfig
    train: np.ndarray        # (n_train, 2) int64 (user, item)
    test: np.ndarray         # (n_test, 2) int64
    user_coords: np.ndarray  # (I, 2) float32
    user_city: np.ndarray    # (I,) int
    item_city: np.ndarray    # (J,) int

    @property
    def n_users(self) -> int:
        return self.config.n_users

    @property
    def n_items(self) -> int:
        return self.config.n_items


def _zipf_sizes(n_bins: int, total: int, a: float,
                rng: np.random.Generator) -> np.ndarray:
    w = 1.0 / np.arange(1, n_bins + 1) ** a
    w = w / w.sum()
    return np.maximum(rng.multinomial(total, w), 1)


def generate(cfg: DataConfig) -> Dataset:
    rng = np.random.default_rng(cfg.seed)
    I, J, C = cfg.n_users, cfg.n_items, cfg.n_cities

    centers = rng.uniform(0.0, 10.0 * np.sqrt(C), size=(C, 2))
    user_city = np.repeat(np.arange(C), _zipf_sizes(C, I, 0.8, rng))[:I]
    item_city = np.repeat(np.arange(C), _zipf_sizes(C, J, 0.8, rng))[:J]
    rng.shuffle(user_city)
    rng.shuffle(item_city)
    user_coords = centers[user_city] + rng.normal(0, 1.0, size=(I, 2))
    item_coords = centers[item_city] + rng.normal(0, 1.0, size=(J, 2))

    K = cfg.latent_dim
    city_taste = rng.normal(0, 1.0, size=(C, K))
    proj = rng.normal(0, cfg.taste_spatial_scale, size=(2, K))
    u_true = (city_taste[user_city] + user_coords @ proj
              + cfg.idiosyncrasy * rng.normal(0, 1, (I, K)))
    v_true = (city_taste[item_city] + item_coords @ proj
              + 0.3 * rng.normal(0, 1, (J, K)))

    user_act = _zipf_sizes(I, cfg.n_ratings, 1.1, rng)
    log_pop = cfg.popularity_scale * (-np.log(np.arange(1, J + 1)))
    rng.shuffle(log_pop)

    pairs = set()
    records = []
    items_by_city = [np.flatnonzero(item_city == c) for c in range(C)]
    all_items = np.arange(J)
    for i in range(I):
        home = items_by_city[user_city[i]]
        for _ in range(int(user_act[i])):
            pool = (home if (rng.random() > cfg.cross_city_frac
                             and len(home) > 0) else all_items)
            dist = np.linalg.norm(item_coords[pool] - user_coords[i], axis=-1)
            logits = (0.5 * (v_true[pool] @ u_true[i]) + log_pop[pool]
                      - cfg.distance_weight * dist)
            p = np.exp(logits - logits.max())
            p /= p.sum()
            j = int(rng.choice(pool, p=p))
            if (i, j) not in pairs:
                pairs.add((i, j))
                records.append((i, j))
    records = np.array(records, dtype=np.int64)

    perm = rng.permutation(len(records))
    n_test = max(1, int(round(cfg.test_frac * len(records))))
    return Dataset(cfg, records[perm[n_test:]], records[perm[:n_test]],
                   user_coords.astype(np.float32), user_city, item_city)


def from_config(config: dict) -> Dataset:
    """The dataset a configuration file's ``data`` block describes."""
    return generate(DataConfig(**config["data"]))
