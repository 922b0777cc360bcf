"""Potts grid MRFs over a synthetic image, segmented from scribbles.

The unary energies come from a two-blob ground truth plus Gaussian
noise drawn from the file's ``image`` seed (a copy of the generator the
program's examples use); the pairwise term is the file's Potts
``beta``.  An evidence *pattern* is a scribble: straight strokes of
clamped pixels labelled from the ground truth, each one compiled
program, warmed in set-up.  A request sends its pattern's strokes and
asks for the marginals of some free sites.

Mix keys read here: ``fresh_strokes`` (a request that follows another
of the same user adds this many strokes to the last one's, so every
request is a new pattern).
"""
from __future__ import annotations

import numpy as np


def blob_image(h: int, w: int, *, seed: int, noise: float):
    """(unary (h, w, 2) float32, truth (h, w) int32): a two-blob ground
    truth plus Gaussian noise, Gaussian unaries for the means 0 and 1."""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:h, 0:w]
    cy, cx = h * 0.55, w * 0.5
    blob = (((yy - cy) / (0.33 * h)) ** 2
            + ((xx - cx) / (0.28 * w)) ** 2) < 1.0
    blob |= (((yy - h * 0.25) / (0.12 * h)) ** 2
             + ((xx - cx) / (0.10 * w)) ** 2) < 1.0
    truth = blob.astype(np.int32)
    img = truth + rng.normal(0, noise, (h, w))
    means = np.array([0.0, 1.0])
    unary = ((img[..., None] - means[None, None, :]) ** 2
             / (2 * noise ** 2)).astype(np.float32)
    return unary, truth


def scribble_mask(h: int, w: int, rng: np.random.Generator,
                  n_strokes: int) -> np.ndarray:
    """Straight strokes of clamped pixels on an (h, w) canvas (a copy of
    the program's ``serve/cli.py`` generator)."""
    mask = np.zeros((h, w), bool)
    for _ in range(n_strokes):
        r, c = int(rng.integers(h)), int(rng.integers(w))
        length = int(rng.integers(2, max(3, min(h, w) // 2) + 1))
        if rng.integers(2):
            mask[r, c:min(c + length, w)] = True
        else:
            mask[r:min(r + length, h), c] = True
    return mask


class Family:
    def __init__(self, config: dict):
        if config["pairwise"] != "potts" or config["image"]["kind"] != "blob":
            raise ValueError("this family is the Potts grid on a blob image")
        self.config = config
        img = config["image"]
        self.h, self.w = config["height"], config["width"]
        unary, self.truth = blob_image(self.h, self.w, seed=img["seed"],
                                       noise=img["noise"])
        if unary.shape[-1] != config["n_labels"]:
            raise ValueError("the blob image has 2 labels")
        # (unary, beta): what the plain reference reads
        self.data = (unary, float(config["beta"]))
        p = config["patterns"]
        rng = np.random.default_rng(p["seed"])
        self.masks = [scribble_mask(self.h, self.w, rng, p["strokes"])
                      for _ in range(p["count"])]
        self.patterns = [self._sites(m) for m in self.masks]

    def _sites(self, mask: np.ndarray) -> list[list[int]]:
        rs, cs = np.nonzero(mask)
        return [[int(r), int(c), int(self.truth[r, c])]
                for r, c in zip(rs, cs)]

    def program(self):
        from repro.pgm.graph import MRFGrid

        unary, beta = self.data
        return MRFGrid.potts(unary, beta)

    def strata(self, p: int) -> int:
        return 1

    def variables(self, p: int) -> list[int]:
        """The sites (flat ids) a request on pattern ``p`` may ask."""
        return np.flatnonzero(~self.masks[p].reshape(-1)).tolist()

    def request(self, mix: dict, p: int, rng: np.random.Generator,
                n_query: int, stratum: int | None = None,
                prev: dict | None = None,
                query: list | None = None) -> dict:
        mask = self.masks[p]
        if prev is not None and mix.get("fresh_strokes"):
            mask = np.zeros_like(mask)
            for r, c, _ in prev["mask_sites"]:
                mask[r, c] = True
            mask |= scribble_mask(self.h, self.w, rng, mix["fresh_strokes"])
        pick = (query if query is not None else
                rng.choice(np.flatnonzero(~mask.reshape(-1)), size=n_query,
                           replace=False))
        return {"v": 2, "network": self.config["name"],
                "n_samples": int(self.config["n_samples"]),
                "mask_sites": (self.patterns[p] if mask is self.masks[p]
                               else self._sites(mask)),
                "query_sites": [[int(v) // self.w, int(v) % self.w]
                                for v in pick]}
