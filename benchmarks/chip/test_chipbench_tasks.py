"""The causal-LM task on the CPU at a tiny size: its job, bins, loss and
compensatory row, and a toy language model followed through three rounds by
the plain reference."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import run
import workload

SEED = 2 ** 31 + 23
SIZES = {"name": "toy-lm", "task": "causal-lm", "vocab_size": 64}
TRAFFIC = {
    "fft": {"n_clients": 6},
    "data": {"partition": "group_domains", "seq_len": 16, "private_sequences": 60,
             "public_sequences": 12, "test_sequences": 24, "group_size": 2,
             "buckets": 8, "hop_prob": 0.8},
}


def _task():
    return run.load_task("causal-lm")


def _job(seed=SEED, traffic=TRAFFIC):
    return _task().make_job(traffic, SIZES, workload.seed_key(seed))


def test_job_shapes_and_dtypes():
    job = _job()
    d = TRAFFIC["data"]
    for split, n in ((job.public, d["public_sequences"]), (job.private, d["private_sequences"]),
                     (job.test, d["test_sequences"])):
        assert split.x.shape == (n, d["seq_len"]) and split.x.dtype == jnp.int32
        assert split.y.shape == (n, d["seq_len"]) and split.y.dtype == np.int32
        assert isinstance(split.y, np.ndarray)
        assert 0 <= int(split.x.min()) and int(split.x.max()) < SIZES["vocab_size"]
    assert job.n_classes == d["buckets"]
    assert len(job.client_indices) == TRAFFIC["fft"]["n_clients"]
    rows = np.sort(np.concatenate(job.client_indices))
    assert rows.tolist() == list(range(d["private_sequences"]))
    # clients 2g and 2g+1 hold the rows of domain g = row mod 3
    for c, ix in enumerate(job.client_indices):
        assert set((ix % 3).tolist()) == {c // 2}


def test_job_is_determined_by_the_seed():
    a, b, c = _job(), _job(), _job(SEED + 1)
    for s in ("public", "private", "test"):
        assert np.array_equal(np.asarray(getattr(a, s).x), np.asarray(getattr(b, s).x))
        assert np.array_equal(getattr(a, s).y, getattr(b, s).y)
        assert getattr(a, s).x.shape == getattr(c, s).x.shape
    assert not np.array_equal(np.asarray(a.private.x), np.asarray(c.private.x))
    assert all(np.array_equal(p, q) for p, q in zip(a.client_indices, c.client_indices))


def test_targets_are_the_inputs_shifted_by_one():
    job = _job()
    for s in (job.public, job.private, job.test):
        assert np.array_equal(np.asarray(s.x)[:, 1:], s.y[:, :-1])


def test_domains_differ_in_their_bigram_statistics():
    task = _task()
    traffic = dict(TRAFFIC, data=dict(TRAFFIC["data"], private_sequences=600, seq_len=64))
    job = task.make_job(traffic, SIZES, workload.seed_key(SEED))
    rows = np.concatenate([np.asarray(job.private.x), job.private.y[:, -1:]], axis=1)
    vocab, dom = SIZES["vocab_size"], np.arange(len(rows)) % 3
    share = np.zeros((3, 3))
    for d in range(3):
        prev, nxt = rows[dom == d, :-1], rows[dom == d, 1:]
        for e in range(3):
            share[d, e] = np.mean(nxt == (7 * prev + task.stride(e, vocab)) % vocab)
    hop = TRAFFIC["data"]["hop_prob"]
    for d in range(3):
        assert abs(share[d, d] - (hop + (1 - hop) / vocab)) < 0.03, share
        for e in range(3):
            if e != d:
                assert share[d, e] < 0.05, share


def test_histograms_equal_the_program_token_histogram():
    from repro.data.tokens import token_class_histogram
    task, job = _task(), _job()
    server, clients = task.histograms(job)
    b = job.n_classes
    assert np.array_equal(server, token_class_histogram(job.public.y, b))
    for c, ix in enumerate(job.client_indices):
        assert np.array_equal(clients[c], token_class_histogram(job.private.y[ix], b))
    assert server.sum() == job.public.y.size


def test_missing_hist_counts_the_public_rows_with_a_missing_bin():
    task, job = _task(), _job()
    b = task.bins(job.public.y, job.n_classes)
    missing = np.array([3])
    got = task.missing_hist(job.public.y, missing, job.n_classes)
    want = np.zeros(job.n_classes, np.int64)
    for row in b:
        if 3 in row:
            want += np.bincount(row, minlength=job.n_classes)
    assert np.array_equal(got, want) and got.sum() > 0
    assert task.missing_hist(job.public.y, np.array([], np.int64), job.n_classes).sum() == 0


def test_loss_is_the_mean_over_every_position():
    task = _task()
    logits = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 7)) * 3.0
    y = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (3, 5), 0, 7))
    lg = np.asarray(logits, np.float64)
    want = []
    for n in range(3):
        for s in range(5):
            z = lg[n, s]
            want.append(np.log(np.sum(np.exp(z - z.max()))) + z.max() - z[y[n, s]])
    assert float(task.loss(logits, jnp.asarray(y))) == pytest.approx(np.mean(want), rel=1e-5)


def test_flops_per_sample_counts_one_sequence():
    mod = types.SimpleNamespace(train_flops=lambda sizes, seq: 6.0 * 1000 * seq)
    assert _task().flops_per_sample(mod, SIZES, TRAFFIC) == 6.0 * 1000 * 16


def _toy_lm(width=16):
    def reference_logits(sizes, base, t, x):
        return t["emb"][x] @ t["head"]

    def init(key):
        v = SIZES["vocab_size"]       # a zero head: every first prediction is uniform
        return {"emb": jax.random.normal(key, (v, width)), "head": jnp.zeros((width, v))}

    return types.SimpleNamespace(reference_logits=reference_logits), init


def test_reference_follows_a_toy_lm_and_its_loss_falls():
    task, job = _task(), _job()
    mod, init = _toy_lm()
    w0 = init(jax.random.PRNGKey(5))
    server, clients = task.histograms(job)
    keys = iter(jax.random.split(jax.random.PRNGKey(6), 64))
    rounds = []
    # no client connects in round 3: every bin is missing, so the compensatory row counts
    for connected in ([0, 1, 2, 3, 4, 5], [0, 2, 4], []):
        ups = [reference.Update("server", -1, job.public.x, job.public.y, next(keys))]
        ups.append(reference.Update("comp", -1, job.public.x, job.public.y, next(keys)))
        for c in connected:
            ix = job.client_indices[c]
            ups.append(reference.Update("client", c, job.private.x[ix], job.private.y[ix],
                                        next(keys)))
        rounds.append(reference.Round(updates=ups, lr=1.0))
    ws = reference.follow(mod, SIZES, task, {}, w0, rounds, server_hist=server,
                          client_hists=clients, public_y=job.public.y, steps=4, batch=8)
    assert len(ws) == 3

    def test_loss(w):
        return float(task.loss(mod.reference_logits(SIZES, {}, w, job.test.x), job.test.y))

    losses = [test_loss(w0)] + [test_loss(w) for w in ws]
    assert losses[0] == pytest.approx(np.log(SIZES["vocab_size"]), rel=1e-5)
    assert losses[0] > losses[1] > losses[2] > losses[3], losses
    assert losses[3] < losses[0] - 0.2, losses
