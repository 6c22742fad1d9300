"""Causal language modelling: each row is a token sequence of one domain's
bigram process, and the model predicts every next token.

The traffic's ``data`` holds ``seq_len`` S, ``private_sequences``,
``public_sequences``, ``test_sequences``, ``group_size`` G, ``buckets`` B,
``hop_prob`` and ``partition: "group_domains"``; the configuration holds
``vocab_size`` V.

* A row holds S + 1 tokens: the first uniform, then, from token t, with
  probability ``hop_prob`` the domain's hop (7 t + stride_d) mod V, where
  stride_d = (2 d + 3) mod (V - 1) + 1, and otherwise a uniform token. All
  rows are drawn on the device from the seed in one jitted scan over
  positions. The inputs are the first S tokens, the targets the last S.
* Row i of a split belongs to domain i mod D, with D = ceil(clients / G);
  clients gG..gG+G-1 split the private rows of domain g in order, and the
  public rows cycle through the domains. The layout is fixed, so every seed
  gives the same shapes; the seed changes the tokens only.
* A histogram bin is a hash bucket of target tokens, bin(t) =
  (t * 2654435761 mod 2^31) mod B, so the FedAuto rows count targets.
* The loss is the mean over all N x S positions of -log softmax(logits)[y].
* A sample is a sequence: the FLOPs per sample are those of one sequence of
  S tokens.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from workload import Job, Split

HASH = 2654435761


def n_domains(traffic: Dict[str, Any]) -> int:
    size = traffic["data"]["group_size"]
    return (traffic["fft"]["n_clients"] + size - 1) // size


def _domains(n: int, n_dom: int) -> np.ndarray:
    return (np.arange(n) % n_dom).astype(np.int32)


def stride(domain, vocab: int):
    return (2 * domain + 3) % (vocab - 1) + 1


@functools.partial(jax.jit, static_argnames=("length", "vocab", "hop_prob"))
def _draw(key, domain, *, length, vocab, hop_prob):
    """(n, length) int32 rows, one of ``domain``'s process each."""
    n = domain.shape[0]
    hop = stride(domain, vocab)
    k0, ks = jax.random.split(key)

    def step(t, k):
        kh, ku = jax.random.split(k)
        jump = jax.random.uniform(kh, (n,)) < hop_prob
        t = jnp.where(jump, (7 * t + hop) % vocab,
                      jax.random.randint(ku, (n,), 0, vocab, jnp.int32))
        return t, t

    first = jax.random.randint(k0, (n,), 0, vocab, jnp.int32)
    _, rest = jax.lax.scan(step, first, jax.random.split(ks, length - 1))
    return jnp.concatenate([first[:, None], rest.T], axis=1)


def make_job(traffic: Dict[str, Any], sizes: Dict[str, Any], key) -> Job:
    d = traffic["data"]
    if d["partition"] != "group_domains":
        raise ValueError(f"unknown partition {d['partition']!r}")
    vocab, seq = sizes["vocab_size"], d["seq_len"]
    if vocab < 2:
        raise ValueError("vocab_size must be at least 2")
    n_dom = n_domains(traffic)
    doms = [_domains(n, n_dom) for n in (d["public_sequences"], d["private_sequences"],
                                         d["test_sequences"])]
    rows = _draw(key, jnp.asarray(np.concatenate(doms)), length=seq + 1, vocab=vocab,
                 hop_prob=float(d["hop_prob"]))
    ys = np.asarray(rows[:, 1:])
    splits = []
    start = 0
    for dom in doms:
        stop = start + len(dom)
        splits.append(Split(x=rows[start:stop, :seq], y=ys[start:stop]))
        start = stop
    pub, priv, test = splits
    return Job(public=pub, private=priv, test=test,
               client_indices=group_domains(doms[1], traffic), n_classes=d["buckets"])


def group_domains(domains: np.ndarray, traffic: Dict[str, Any]) -> List[np.ndarray]:
    """Clients g*G..g*G+G-1 split the rows of domain g in order."""
    n_clients, size = traffic["fft"]["n_clients"], traffic["data"]["group_size"]
    out = []
    for g in range(n_domains(traffic)):
        pool = np.where(domains == g)[0]
        members = min(size, n_clients - g * size)
        if len(pool) < members:
            raise ValueError(f"domain {g} has {len(pool)} private rows for {members} clients")
        out.extend(np.array_split(pool, members))
    return out


def bins(tokens, n_bins: int) -> np.ndarray:
    """The hash bucket of each token, in the tokens' shape."""
    t = np.asarray(tokens).astype(np.int64)
    return (t * HASH % (2 ** 31)) % n_bins


def _hist(tokens, n_bins: int) -> np.ndarray:
    return np.bincount(bins(tokens, n_bins).reshape(-1), minlength=n_bins)


def histograms(job: Job):
    """(server histogram, per-client histograms) of target-token bins."""
    b = job.n_classes
    server = _hist(job.public.y, b)
    clients = np.stack([_hist(job.private.y[ix], b) for ix in job.client_indices])
    return server, clients


def loss(logits, y):
    """Mean next-token cross-entropy of (n, S, V) logits against (n, S) targets."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))


def missing_hist(public_y: np.ndarray, missing_bins: np.ndarray, n_bins: int) -> np.ndarray:
    """Target bins of the public rows that hold a target in a missing bin."""
    b = bins(public_y, n_bins)
    rows = np.isin(b, missing_bins).any(axis=1)
    return np.bincount(b[rows].reshape(-1), minlength=n_bins)


def flops_per_sample(mod, sizes: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    """Training FLOPs of one sequence of ``seq_len`` tokens."""
    return mod.train_flops(sizes, traffic["data"]["seq_len"])
