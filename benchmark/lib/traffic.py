"""The one traffic generator. A traffic mix is a JSON file of parameters
under `benchmark/traffic/`; everything drawn is a pure function of
(`--seed`, index).

A unit of traffic is a GROUP: one prompt and `n_samples` completions of it
(GRPO's group). What the seed changes and what it does not:

- The SIZES are the same multiset for every seed. The output lengths of
  `epoch_groups` consecutive groups (an epoch: `epoch_groups * n_samples`
  samples) are exactly the mid-quantiles of the file's clipped output-length
  distribution, tail and clip included. They are cut into `n_samples` strata
  (shortest to longest) and every group of the epoch gets one length from each
  stratum, so every group has its straggler, as a GRPO group has, but not the
  same one. With `epoch_groups` 1 (the default) every group holds the same
  `n_samples` mid-quantiles. The prompt lengths of `prompt_strata` consecutive
  groups are the mid-quantiles of the uniform prompt-length range. Every seed
  therefore offers the same work and packs into the same padded shapes: runs
  with different seeds compare, and no seed meets a shape the compile cache
  has never seen.
- The seed draws the token ids, which group of an epoch gets which length of a
  stratum, the order of the output lengths inside a group, the order of the
  prompt lengths inside a cycle of groups and the order of the first cohort's
  scales.

A random model never emits EOS, so each request's output length is pinned by
the caller (`min_new_tokens == max_new_tokens`).
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def output_lengths(spec: dict, n: int) -> list[int]:
    """The n mid-quantiles ((i + 0.5) / n) of a clipped log-normal."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown output_len dist {spec['dist']!r}")
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        x = spec["median"] * math.exp(spec["sigma"] * z)
        out.append(int(min(max(round(x), spec["lo"]), spec["hi"])))
    return out


def prompt_lengths(spec: dict, n: int) -> list[int]:
    """The n mid-quantiles of uniform [lo, hi]."""
    return [int(round(spec["lo"] + (i + 0.5) / n * (spec["hi"] - spec["lo"])))
            for i in range(n)]


@dataclass
class Group:
    index: int
    prompt: np.ndarray  # int32 token ids
    output_lens: list[int]


class Traffic:
    def __init__(self, params: dict, vocab_size: int, seed: int):
        self.p = params
        self.vocab_size = int(vocab_size)
        self.seed = int(seed)
        self.n_samples = int(params["n_samples"])
        self._epoch = int(params.get("epoch_groups", 1))
        population = output_lengths(params["output_len"], self._epoch * self.n_samples)
        self._out_strata = [population[j * self._epoch:(j + 1) * self._epoch]
                            for j in range(self.n_samples)]
        self._strata = int(params.get("prompt_strata", 8))
        self._prompts = prompt_lengths(params["prompt_len"], self._strata)

    def _rng(self, *stream: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, *stream])

    def group(self, i: int, scale: float = 1.0) -> Group:
        """Group i; `scale` < 1 shortens its output lengths (never under the
        distribution's floor)."""
        cycle, k = divmod(i, self._strata)
        plen = self._prompts[int(self._rng(1, cycle).permutation(self._strata)[k])]
        rng = self._rng(2, i)
        prompt = rng.integers(1, self.vocab_size, plen, dtype=np.int32)
        epoch, member = divmod(i, self._epoch)
        lens = [stratum[int(self._rng(5, epoch, j).permutation(self._epoch)[member])]
                for j, stratum in enumerate(self._out_strata)]
        lens = [lens[j] for j in rng.permutation(self.n_samples)]
        if scale != 1.0:
            floor = int(self.p["output_len"]["lo"])
            lens = [max(floor, int(n * scale)) for n in lens]
        return Group(i, prompt, lens)

    def cohort_scales(self, n: int) -> list[float]:
        """Output-length scales for the n groups a closed loop starts at
        once: the mid-quantiles of uniform [first_cohort_min_scale, 1] in
        seeded order, so that their completions are spread from the first
        second instead of arriving in waves."""
        lo = float(self.p.get("first_cohort_min_scale", 1.0))
        order = self._rng(3).permutation(n)
        return [lo + (int(r) + 0.5) / n * (1.0 - lo) for r in order]

    def train_batch(self, j: int, groups_per_batch: int) -> dict:
        """Padded [B, T] batch j of a trainer-only cell, in the layout
        `RLVRWorkflow` emits: groups j*G .. j*G+G-1, each sample the prompt
        followed by seeded completion tokens. Behaviour log-probabilities are
        filled in by the caller (from the policy itself: on-policy data)."""
        seqs = []
        for g in range(j * groups_per_batch, (j + 1) * groups_per_batch):
            grp = self.group(g)
            rng = self._rng(4, g)
            for n_out in grp.output_lens:
                comp = rng.integers(1, self.vocab_size, n_out, dtype=np.int32)
                ids = np.concatenate([grp.prompt, comp])
                n_in = len(grp.prompt)
                seqs.append(dict(
                    input_ids=ids,
                    loss_mask=np.r_[np.zeros(n_in, np.int32), np.ones(n_out, np.int32)],
                    logprobs=np.zeros(len(ids), np.float32),
                    versions=np.r_[np.full(n_in, -1, np.int32), np.zeros(n_out, np.int32)],
                    rewards=np.float32(0.0),
                    begin_of_answer=np.int32(n_in),
                ))
        T = max(len(s["input_ids"]) for s in seqs)
        out = {}
        for k in seqs[0]:
            vals = [np.asarray(s[k]) for s in seqs]
            if vals[0].ndim == 1:
                vals = [np.pad(v, (0, T - len(v))) for v in vals]
            out[k] = np.stack(vals)
        out["attention_mask"] = np.stack(
            [np.arange(T) < len(s["input_ids"]) for s in seqs])
        return out


def longest_sequence(params: dict, multiple: int = 128) -> int:
    """The longest prompt + completion this traffic can make, rounded up: the
    one shape every seed's check against the reference is padded to."""
    n = int(params["prompt_len"]["hi"]) + int(params["output_len"]["hi"])
    return -(-n // multiple) * multiple


def batch_lengths(batch: dict) -> list[int]:
    return [int(n) for n in np.asarray(batch["attention_mask"]).sum(axis=1)]
