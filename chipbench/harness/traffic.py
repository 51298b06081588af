"""The one generator of traffic: reads a mix's parameters, draws from the
seed.

Sizes are the same set for every seed (stratified quantiles of the stated
distributions); the seed draws the tokens and the order.  So two seeds do
the same amount of work and differ in which token ids and in what order.
"""

import statistics

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *stream])


def train_batch(traffic: dict, vocab: int, seed: int, step: int) -> dict:
    """Uniform token ids over the vocabulary; labels are the next ids, so
    every row differs and no row is padding."""
    B, S = traffic["batch"], traffic["seq_len"]
    toks = rng(seed, 0, step).integers(0, vocab, (B, S + 1), dtype=np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def _lognormal_quantiles(dist: dict, n: int) -> np.ndarray:
    """n stratified draws: the (i + 1/2)/n quantiles, clipped, rounded."""
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n)
                  for i in range(n)])
    x = np.exp(np.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


class Requests:
    """Per client, its k-th request: prompt token ids and answer length."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.clients = traffic["clients"]
        n = self.clients * traffic["requests_per_client"]
        prompt = _lognormal_quantiles(traffic["prompt"], n)
        answer = _lognormal_quantiles(traffic["answer"], n)
        answer = answer[np.random.default_rng(0).permutation(n)]
        order = rng(seed, 1).permutation(n)
        self.prompt_len, self.answer_len = prompt[order], answer[order]
        self.vocab, self.seed, self.n = vocab, seed, n

    def get(self, client: int, k: int):
        i = (k * self.clients + client) % self.n
        prompt = rng(self.seed, 2, client, k).integers(
            0, self.vocab, self.prompt_len[i], dtype=np.int32)
        return prompt, int(self.answer_len[i])
