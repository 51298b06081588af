"""The comparison that decides `correct`: the program's readings against
the plain reference's, each number against its limit."""

import statistics

import numpy as np

from . import traffic as gen


def worst_leaf(prog: dict, ref: dict, keep) -> float:
    """Largest gap between the program's and the reference's norm of a
    leaf, over the larger of that leaf's reference norm and the median
    leaf's."""
    med = statistics.median(ref[k] for k in keep)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def moved_leaves(ref_grad: dict) -> list:
    """Leaves whose reference gradient is more than a thousandth of the
    median leaf's; the rest move under Adam by round-off alone."""
    med = statistics.median(ref_grad.values())
    return sorted(k for k, v in ref_grad.items() if v > 1e-3 * med)


def train(prog: dict, ref: dict) -> dict:
    keep = moved_leaves(ref["grad"])
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["loss"], ref["loss"])),
        "grad_gap": worst_leaf(prog["grad"], ref["grad"], keep),
        "change_gap": worst_leaf(prog["change"], ref["change"], keep),
    }


def sample(done: list, k: int, seed: int) -> list:
    """k finished requests drawn from the seed, the longest among them."""
    if not done:
        raise ValueError("no request finished in the window")
    longest = max(range(len(done)), key=lambda i: len(done[i][0])
                  + len(done[i][1]))
    rest = [i for i in range(len(done)) if i != longest]
    pick = gen.rng(seed, 3).permutation(rest)[:k - 1]
    return [done[i] for i in [longest, *sorted(pick)]]


def served_positions(requests: list, width: int):
    """(seqs, targets, mask): each request's prompt and served tokens bar
    the last as the tokens fed, and at the position of each served token
    that token."""
    n = len(requests)
    seqs = np.zeros((n, width), np.int32)
    targets = np.zeros((n, width), np.int32)
    mask = np.zeros((n, width), bool)
    for i, (prompt, tokens) in enumerate(requests):
        fed = np.concatenate([prompt, tokens[:-1]])
        seqs[i, :len(fed)] = fed
        at = len(prompt) - 1 + np.arange(len(tokens))
        targets[i, at], mask[i, at] = tokens, True
    return seqs, targets, mask


def serve(config: dict, traffic: dict, seed: int, done: list, k: int,
          control: bool = False) -> dict:
    from reference import serve as ref
    width = traffic["prompt"]["max"] + traffic["answer"]["max"] - 1
    seqs, targets, mask = served_positions(sample(done, k, seed), width)
    gaps = ref.gaps(config, seed, seqs, targets, control)
    return {"logit_gap": float(gaps[mask].max()),
            "served_tokens": int(mask.sum())}
