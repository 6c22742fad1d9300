"""Image classification: CIFAR-100-shaped synthetic images, one class label
per image.

Data is made on the device in one jitted call from the seed: each class is a
smooth random prototype plus Gaussian noise. Labels follow a fixed layout
(row i of a split has class i mod C), so every seed gives the same class
counts, the same partition sizes and the same shapes; the seed changes the
values only. The partition is the paper's ``group_classes`` scheme: clients
4g..4g+3 share the classes of group g. A histogram bin is a class.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from workload import Job, Split


def _labels(n: int, n_classes: int) -> np.ndarray:
    return (np.arange(n) % n_classes).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("hw", "ch", "c", "noise"))
def _draw(key, y, *, hw, ch, c, noise):
    kb, kp, kn = jax.random.split(key, 3)
    base = jax.random.normal(kb, (hw // 4, hw // 4, ch))
    protos = 0.35 * base + jax.random.normal(kp, (c, hw // 4, hw // 4, ch))
    protos = jnp.repeat(jnp.repeat(protos, 4, axis=1), 4, axis=2)
    x = protos[y] + noise * jax.random.normal(kn, (y.shape[0], hw, hw, ch))
    return x.astype(jnp.float32)


def make_job(traffic: Dict[str, Any], sizes: Dict[str, Any], key) -> Job:
    d = traffic["data"]
    c = sizes["num_classes"]
    n_pub, n_priv, n_test = c * d["public_per_class"], d["private_samples"], d["test_samples"]
    ys = [_labels(n, c) for n in (n_pub, n_priv, n_test)]
    x = _draw(key, jnp.asarray(np.concatenate(ys)),
              hw=sizes.get("data_image_size", sizes["image_size"]),
              ch=sizes["channels"], c=c, noise=float(d["noise"]))
    splits = []
    start = 0
    for y in ys:
        splits.append(Split(x=x[start:start + len(y)], y=y))
        start += len(y)
    pub, priv, test = splits
    return Job(public=pub, private=priv, test=test,
               client_indices=group_classes(priv.y, traffic), n_classes=c)


def group_classes(labels: np.ndarray, traffic: Dict[str, Any]) -> List[np.ndarray]:
    """Clients g*G..g*G+G-1 split the rows of classes g*K..g*K+K-1 in order."""
    d = traffic["data"]
    if d["partition"] != "group_classes":
        raise ValueError(f"unknown partition {d['partition']!r}")
    n_clients, size, per = (traffic["fft"]["n_clients"], d["group_size"],
                            d["classes_per_group"])
    out = []
    for g in range((n_clients + size - 1) // size):
        cls = np.arange(g * per, (g + 1) * per)
        pool = np.where(np.isin(labels, cls))[0]
        members = min(size, n_clients - g * size)
        out.extend(np.array_split(pool, members))
    return out


def histograms(job: Job):
    """(server histogram, per-client histograms) of class counts."""
    c = job.n_classes
    server = np.bincount(job.public.y, minlength=c)
    clients = np.stack([np.bincount(job.private.y[ix], minlength=c)
                        for ix in job.client_indices])
    return server, clients


def loss(logits, y):
    """Mean cross-entropy of (n, C) logits against (n,) labels."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def missing_hist(public_y: np.ndarray, missing_bins: np.ndarray, n_bins: int) -> np.ndarray:
    """Class counts of the public rows whose class no participant holds."""
    return np.bincount(public_y[np.isin(public_y, missing_bins)], minlength=n_bins)


def flops_per_sample(mod, sizes: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    """Training FLOPs of one image."""
    return mod.train_flops(sizes)
