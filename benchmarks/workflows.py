"""The paper's four evaluation workflows (§6.2), rebuilt on Helix-JAX.

Each factory builds a Workflow from a knob dataclass; ``mutate`` applies a
random edit of a given kind (DPR / LI / PPR), and ``ITERATION_FREQS`` encode
the per-domain edit-type frequencies from the paper's applied-ML survey
([78], used in §6.3): census is PPR-heavy (social-science result analysis),
NLP is DPR-only, genomics is L/I-heavy, MNIST is mixed.

All compute is real (JAX/numpy): CSV parsing, learned discretization,
logistic-regression training, skip-gram embeddings, k-means, a transformer
encoder as the expensive "NLP parse", random-FFT features (nondeterministic,
as in KeystoneML's MNIST pipeline).
"""
from __future__ import annotations

import dataclasses
import functools
import io
from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp

from repro import configs
from repro.core import Workflow
from repro.data import synth, tabular
from repro.launch import shapes
from repro.launch.mesh import make_local_mesh
from repro.models.config import ArchConfig
from repro.train import steps as train_steps


# ---------------------------------------------------------------------------
# small JAX learners shared by the workflows
# ---------------------------------------------------------------------------
def train_logreg(X: np.ndarray, y: np.ndarray, reg: float, iters: int = 300,
                 lr: float = 0.5) -> np.ndarray:
    Xj, yj = jnp.asarray(X), jnp.asarray(y, jnp.float32)

    def loss(w):
        logits = Xj @ w[:-1] + w[-1]
        ce = jnp.mean(jnp.logaddexp(0.0, logits) - yj * logits)
        return ce + reg * jnp.sum(w[:-1] ** 2)

    w = jnp.zeros(X.shape[1] + 1)
    g = jax.jit(jax.grad(loss))
    for _ in range(iters):
        w = w - lr * g(w)
    return np.asarray(w)


def logreg_predict(w: np.ndarray, X: np.ndarray) -> np.ndarray:
    return (X @ w[:-1] + w[-1] > 0).astype(np.int32)


def train_embeddings(docs: np.ndarray, vocab: int, dim: int, epochs: int,
                     seed: int = 0) -> np.ndarray:
    """Skip-gram-ish embeddings via jitted SGD over co-occurrence pairs."""
    rng = np.random.default_rng(seed)
    centers = docs[:, :-1].reshape(-1)
    contexts = docs[:, 1:].reshape(-1)
    neg = rng.integers(0, vocab, len(centers))
    E = jnp.asarray(rng.normal(0, 0.1, (vocab, dim)), jnp.float32)

    @jax.jit
    def epoch(E):
        def loss(E):
            c = E[centers]
            pos = jnp.sum(c * E[contexts], -1)
            ngs = jnp.sum(c * E[neg], -1)
            return jnp.mean(jnp.logaddexp(0, -pos) + jnp.logaddexp(0, ngs))
        return E - 0.5 * jax.grad(loss)(E)

    for _ in range(epochs):
        E = epoch(E)
    return np.asarray(E)


def kmeans(X: np.ndarray, k: int, iters: int = 25, seed: int = 0
           ) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    C = jnp.asarray(X[rng.choice(len(X), k, replace=False)])
    Xj = jnp.asarray(X)

    @jax.jit
    def step(C):
        d = jnp.sum((Xj[:, None] - C[None]) ** 2, -1)
        assign = jnp.argmin(d, 1)
        onehot = jax.nn.one_hot(assign, k)
        counts = onehot.sum(0)[:, None] + 1e-9
        return (onehot.T @ Xj) / counts, assign

    for _ in range(iters):
        C, assign = step(C)
    return np.asarray(C), np.asarray(assign)


def encoder_parse(docs: np.ndarray, vocab: int, seed: int = 0,
                  dim: int = 128, layers: int = 4) -> np.ndarray:
    """The NLP workflow's expensive 'parse': a transformer encoder over every
    document (stands in for CoreNLP in the paper's IE workflow)."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 2 + 4 * layers)
    E = jax.random.normal(ks[0], (vocab, dim)) * 0.05
    Ws = [tuple(jax.random.normal(ks[2 + 4 * i + j], (dim, dim)) * dim ** -0.5
                for j in range(4)) for i in range(layers)]

    @jax.jit
    def run(tok):
        h = E[tok]
        for wq, wk, wv, wo in Ws:
            q, k_, v = h @ wq, h @ wk, h @ wv
            a = jax.nn.softmax(q @ k_.swapaxes(-1, -2) / dim ** 0.5, -1)
            h = h + (a @ v) @ wo
            h = h / (jnp.linalg.norm(h, axis=-1, keepdims=True) + 1e-6)
        return h

    out = []
    for i in range(0, len(docs), 256):
        out.append(np.asarray(run(jnp.asarray(docs[i:i + 256]))))
    return np.concatenate(out)


# ---------------------------------------------------------------------------
# 1. census (the paper's running example, Fig. 3)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class CensusKnobs:
    n_rows: int = 120_000
    n_buckets: int = 10
    use_interaction: bool = True
    use_hours: bool = True
    reg: float = 0.1
    train_iters: int = 300        # halving resource (SGD steps)
    eval_threshold: float = 0.5   # PPR knob (report formatting)
    eval_metric: str = "accuracy"


def build_census(k: CensusKnobs) -> Workflow:
    wf = Workflow("census")

    def load_csv():
        rows = synth.census_rows(7, k.n_rows)
        buf = io.StringIO()
        cols = sorted(rows)
        for i in range(k.n_rows):
            buf.write(",".join(str(rows[c][i]) for c in cols) + "\n")
        return cols, buf.getvalue()

    raw = wf.source("data", load_csv, config=("census-v1", k.n_rows))

    def parse(raw):
        cols, text = raw
        mat = np.loadtxt(io.StringIO(text), delimiter=",", dtype=np.int64)
        return {c: mat[:, i] for i, c in enumerate(cols)}

    rows = wf.scanner("rows", parse, [raw], config="csv")

    age = wf.extractor("ageExt", lambda r: tabular.standardize(r["age"]),
                       [rows], config="age")
    edu = wf.extractor("eduExt", lambda r: tabular.one_hot(r["education"], 16),
                       [rows], config="edu")
    occ = wf.extractor("occExt", lambda r: tabular.one_hot(r["occupation"], 15),
                       [rows], config="occ")
    cg = wf.extractor("cgExt", lambda r: tabular.standardize(
        np.log1p(r["capital_gain"])), [rows], config="cg")
    sex = wf.extractor("sexExt", lambda r: tabular.one_hot(r["sex"], 2),
                       [rows], config="sex")
    # raceExt exists but is excluded from the synthesizer → pruned (§5.4)
    wf.extractor("raceExt", lambda r: tabular.one_hot(r["race"], 5),
                 [rows], config="race")
    ageb = wf.extractor(
        "ageBucket", lambda r: tabular.one_hot(
            tabular.bucketize(r["age"], k.n_buckets), k.n_buckets),
        [rows], config=("bucket", k.n_buckets))
    feats = [age, edu, occ, cg, sex, ageb]
    if k.use_hours:
        feats.append(wf.extractor(
            "hoursExt", lambda r: tabular.standardize(r["hours"]),
            [rows], config="hours"))
    if k.use_interaction:
        feats.append(wf.extractor(
            "eduXocc", lambda r: tabular.interact(
                tabular.one_hot(r["education"], 16),
                tabular.one_hot(r["occupation"], 15)),
            [rows], config="interact"))

    def make_examples(rows_v, *blocks):
        X, prov = tabular.assemble(
            {f"b{i}": b for i, b in enumerate(blocks)})
        y = rows_v["target"].astype(np.int32)
        n_train = int(0.8 * len(y))
        return dict(X=X, y=y, n_train=n_train, provenance=prov)

    income = wf.synthesizer("income", make_examples, [rows] + feats,
                            config=("examples", len(feats)))

    model = wf.learner(
        "incPred", lambda ex: train_logreg(
            ex["X"][:ex["n_train"]], ex["y"][:ex["n_train"]], k.reg,
            iters=k.train_iters),
        [income], config=("LR", k.reg, k.train_iters))

    preds = wf.learner(
        "predictions", lambda ex, w: logreg_predict(w, ex["X"]),
        [income, model], config="predict")

    def check(ex, p):
        test = slice(ex["n_train"], None)
        yt, pt = ex["y"][test], p[test]
        if k.eval_metric == "accuracy":
            val = float((yt == pt).mean())
        else:  # f1
            tp = float(((yt == 1) & (pt == 1)).sum())
            prec = tp / max(float((pt == 1).sum()), 1)
            rec = tp / max(float((yt == 1).sum()), 1)
            val = 2 * prec * rec / max(prec + rec, 1e-9)
        return {"metric": k.eval_metric, "value": val,
                "threshold_pass": val > k.eval_threshold}

    checked = wf.reducer("checkResults", check, [income, preds],
                         config=("eval", k.eval_metric, k.eval_threshold))
    wf.output(checked)
    return wf


# ---------------------------------------------------------------------------
# 1b. census, daily-retrain variant (chunk-partitioned source — chunks.py)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class IncrementalCensusKnobs:
    """The append-mostly census scenario: ``n_chunks`` daily batches of
    ``rows_per_chunk`` rows; a retrain after a day's append sees one new
    chunk. Featurization dominates the cost by design (wide one-hot
    interactions, per-row → ``incremental="map"``), which is exactly the
    regime where chunk splicing pays: the learner retrains on every
    append regardless, but the feature matrix is 90 %-cached."""

    n_chunks: int = 10
    rows_per_chunk: int = 8_000
    seed: int = 7
    feat_dim: int = 512          # random-feature width (featurize layers)
    feat_layers: int = 8         # cos-layer depth: the dominant, map-safe cost
    reg: float = 0.1
    train_iters: int = 15


def train_logreg_np(X: np.ndarray, y: np.ndarray, reg: float, iters: int,
                    lr: float = 0.5) -> np.ndarray:
    """Binary logistic regression in plain numpy (deterministic, no jit
    compile constant — the daily-retrain bench compares delta vs. cold
    wall-clock, and an XLA compile identical in both runs would wash out
    the splice signal at CI-smoke scale)."""
    X = np.ascontiguousarray(X, np.float32)
    yf = np.asarray(y, np.float32)
    w = np.zeros(X.shape[1], np.float32)
    b = np.float32(0.0)
    n = np.float32(len(y))
    for _ in range(iters):
        z = X @ w + b
        p = np.float32(1.0) / (np.float32(1.0) + np.exp(-z))
        err = p - yf
        w -= np.float32(lr) * (X.T @ err / n
                               + np.float32(2 * reg) * w)
        b -= np.float32(lr) * err.mean()
    return np.concatenate([w, [b]]).astype(np.float64)


def build_census_incremental(k: IncrementalCensusKnobs) -> Workflow:
    descs = tabular.census_chunk_descriptors(k.seed, k.n_chunks,
                                             k.rows_per_chunk)
    wf = Workflow("census_inc")
    rows = wf.source("rows", lambda: tabular.load_census_chunks(descs),
                     chunks=descs)

    # Row-local featurization (map-safe: one_hot / fixed_bucketize and a
    # fixed-weight random-feature expansion depend only on their own row
    # — see tabular.py). The two cos-layers are the deliberately
    # dominant cost: this is the work chunk splicing saves.
    def featurize(r):
        base = np.concatenate([
            tabular.one_hot(r["education"], 16),
            tabular.one_hot(r["occupation"], 15),
            tabular.one_hot(r["sex"], 2),
            tabular.one_hot(tabular.fixed_bucketize(
                r["age"], range(20, 90, 7)), 11),
            tabular.one_hot(tabular.fixed_bucketize(
                r["hours"], range(10, 90, 8)), 11),
        ], axis=1)
        rng = np.random.default_rng(12345)   # fixed weights: deterministic
        w1 = rng.normal(0, 0.3, (base.shape[1], k.feat_dim)
                        ).astype(np.float32)
        b1 = rng.uniform(0, 2 * np.pi, k.feat_dim).astype(np.float32)
        h = np.cos(base @ w1 + b1)
        for _ in range(max(k.feat_layers - 1, 0)):
            w2 = rng.normal(0, 0.1, (k.feat_dim, k.feat_dim)
                            ).astype(np.float32)
            b2 = rng.uniform(0, 2 * np.pi, k.feat_dim).astype(np.float32)
            h = np.cos(h @ w2 + b2)
        return h

    feats = wf.extractor("rowFeats", featurize, [rows],
                         config=("rowfeat-v1", k.feat_dim, k.feat_layers),
                         incremental="map")
    labels = wf.extractor("labels",
                          lambda r: r["target"].astype(np.int32), [rows],
                          config="labels", incremental="map")
    # Column sums — genuinely associative under fn re-application:
    # sum(concat(chunks)) == sum(stack(per-chunk sums)).
    fsum = wf.reducer("featSums", lambda X: np.sum(X, axis=0,
                                                   dtype=np.float64),
                      [feats], config="sums", incremental="assoc_reduce")

    def train(X, y, sums):
        scale = (1.0 / np.sqrt(1.0 + np.abs(sums) / max(len(y), 1))
                 ).astype(np.float32)
        return train_logreg_np(X * scale, y, k.reg, iters=k.train_iters)

    model = wf.learner("incModel", train, [feats, labels, fsum],
                       config=("LRnp", k.reg, k.train_iters))

    def evaluate(X, y, sums, w):
        scale = (1.0 / np.sqrt(1.0 + np.abs(sums) / max(len(y), 1))
                 ).astype(np.float32)
        p = ((X * scale) @ w[:-1] + w[-1] > 0).astype(np.int32)
        return {"accuracy": float((p == y).mean()), "n_rows": len(y)}

    out = wf.reducer("dailyEval", evaluate, [feats, labels, fsum, model],
                     config="eval")
    wf.output(out)
    return wf


def mutate_census(k: CensusKnobs, kind: str, rng: np.random.Generator
                  ) -> CensusKnobs:
    if kind == "DPR":
        choice = rng.integers(0, 3)
        if choice == 0:
            return dataclasses.replace(k, n_buckets=int(rng.integers(4, 16)))
        if choice == 1:
            return dataclasses.replace(k, use_interaction=not k.use_interaction)
        return dataclasses.replace(k, use_hours=not k.use_hours)
    if kind == "LI":
        return dataclasses.replace(k, reg=float(rng.choice(
            [0.01, 0.03, 0.1, 0.3, 1.0])))
    return dataclasses.replace(
        k, eval_threshold=float(rng.uniform(0.4, 0.9)),
        eval_metric=str(rng.choice(["accuracy", "f1"])))


# ---------------------------------------------------------------------------
# 2. genomics (Example 1: embed entities, cluster)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class GenomicsKnobs:
    n_docs: int = 3000
    vocab: int = 4000
    emb_dim: int = 64
    emb_epochs: int = 12
    n_clusters: int = 16
    kb_size: int = 400
    report_top: int = 5


def build_genomics(k: GenomicsKnobs) -> Workflow:
    wf = Workflow("genomics")
    docs = wf.source("articles", lambda: synth.documents(
        11, k.n_docs, 160, k.vocab), config=("docs", k.n_docs, k.vocab))
    kb = wf.source("geneKB", lambda: np.arange(0, k.vocab, k.vocab // k.kb_size,
                                               dtype=np.int32),
                   config=("kb", k.kb_size))
    ents = wf.synthesizer(
        "entities", lambda d, g: np.intersect1d(np.unique(d), g),
        [docs, kb], config="join")
    emb = wf.learner(
        "word2vec", lambda d: train_embeddings(
            d, k.vocab, k.emb_dim, k.emb_epochs),
        [docs], config=("w2v", k.emb_dim, k.emb_epochs))
    gene_emb = wf.extractor("geneVectors", lambda E, e: E[e],
                            [emb, ents], config="gather")
    clusters = wf.learner(
        "kmeans", lambda X: kmeans(X, k.n_clusters),
        [gene_emb], config=("km", k.n_clusters))

    def report(X, cl):
        C, assign = cl
        d = np.linalg.norm(X - C[assign], axis=1)
        sizes = np.bincount(assign, minlength=k.n_clusters)
        top = np.argsort(sizes)[::-1][:k.report_top]
        return {"inertia": float((d ** 2).sum()),
                "top_cluster_sizes": sizes[top].tolist()}

    out = wf.reducer("clusterReport", report, [gene_emb, clusters],
                     config=("report", k.report_top))
    wf.output(out)
    return wf


def mutate_genomics(k: GenomicsKnobs, kind: str, rng) -> GenomicsKnobs:
    if kind == "DPR":
        if rng.random() < 0.5:
            return dataclasses.replace(k, n_docs=int(rng.choice(
                [2000, 3000, 4000])))
        return dataclasses.replace(k, kb_size=int(rng.choice([200, 400, 800])))
    if kind == "LI":
        if rng.random() < 0.5:
            return dataclasses.replace(k, emb_dim=int(rng.choice([32, 64, 96])))
        return dataclasses.replace(k, n_clusters=int(rng.choice([8, 16, 32])))
    return dataclasses.replace(k, report_top=int(rng.integers(3, 10)))


# ---------------------------------------------------------------------------
# 3. NLP / IE (spouse extraction analogue; DPR-only iterations)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class NLPKnobs:
    n_docs: int = 1200
    vocab: int = 2000
    pair_window: int = 8
    feat_dim: int = 64
    reg: float = 0.1


def build_nlp(k: NLPKnobs) -> Workflow:
    wf = Workflow("nlp_ie")
    docs = wf.source("news", lambda: synth.documents(
        23, k.n_docs, 240, k.vocab), config=("docs", k.n_docs))
    kb = wf.source("knownPairs", lambda: np.stack(
        [np.arange(0, 200, 2), np.arange(1, 200, 2)], 1).astype(np.int32),
        config="pairs")
    # The expensive, reusable step (paper §6.5.2 "NLP"): parse everything.
    parsed = wf.scanner("corenlp", lambda d: encoder_parse(d, k.vocab),
                        [docs], config="parse-v1")

    def candidates(d, emb, pairs):
        pset = {tuple(p) for p in pairs.tolist()}
        feats, labels = [], []
        for i in range(len(d)):
            toks = d[i]
            for j in range(0, len(toks) - k.pair_window, k.pair_window):
                a, b = int(toks[j]), int(toks[j + k.pair_window - 1])
                v = np.concatenate([emb[i, j], emb[i, j + k.pair_window - 1]])
                feats.append(v[:k.feat_dim])
                labels.append(1 if (a, b) in pset or (b, a) in pset else 0)
        return np.stack(feats).astype(np.float32), np.asarray(labels, np.int32)

    cand = wf.synthesizer("candidates", candidates, [docs, parsed, kb],
                          config=("cand", k.pair_window, k.feat_dim))
    model = wf.learner(
        "spouseLR", lambda c: train_logreg(c[0], c[1], k.reg, iters=200),
        [cand], config=("LR", k.reg))

    def f1(c, w):
        X, y = c
        p = logreg_predict(w, X)
        tp = float(((y == 1) & (p == 1)).sum())
        prec = tp / max(float((p == 1).sum()), 1)
        rec = tp / max(float((y == 1).sum()), 1)
        return {"f1": 2 * prec * rec / max(prec + rec, 1e-9)}

    out = wf.reducer("scoreF1", f1, [cand, model], config="f1")
    wf.output(out)
    return wf


def mutate_nlp(k: NLPKnobs, kind: str, rng) -> NLPKnobs:
    # paper: the NLP workflow only has DPR iterations
    if rng.random() < 0.5:
        return dataclasses.replace(k, pair_window=int(rng.choice([4, 6, 8, 12])))
    return dataclasses.replace(k, feat_dim=int(rng.choice([32, 64, 128])))


# ---------------------------------------------------------------------------
# 4. MNIST (nondeterministic featurization → little reuse)
# ---------------------------------------------------------------------------
def train_softmax_np(Z: np.ndarray, y: np.ndarray, reg: float, epochs: int,
                     lr: float = 0.5) -> np.ndarray:
    """Softmax regression in plain numpy (BLAS releases the GIL, so tower
    branches using it parallelize across the pipelined executor's
    workers — the jitted jax path serializes on XLA's CPU runtime)."""
    W = np.zeros((Z.shape[1], 10), np.float32)
    n = len(y)
    idx = np.arange(n)
    for _ in range(epochs):
        logits = Z @ W
        logits -= logits.max(1, keepdims=True)
        p = np.exp(logits)
        p /= p.sum(1, keepdims=True)
        p[idx, y] -= 1.0
        W -= lr * (Z.T @ p / n + 2 * reg * W)
    return W


@dataclasses.dataclass(frozen=True)
class MNISTKnobs:
    n_images: int = 12_000
    n_features: int = 512
    # >1 splits featurization into independent random-FFT towers of
    # n_features/n_towers features, each training its own softmax head
    # (KeystoneML-style block solve, ensembled by logit summation) — the
    # DAG branch parallelism the pipelined executor exploits.
    n_towers: int = 1
    reg: float = 1e-3
    epochs: int = 60
    eval_k: int = 1


def build_mnist(k: MNISTKnobs) -> Workflow:
    wf = Workflow("mnist")
    imgs = wf.source("mnist", lambda: synth.images(5, k.n_images),
                     config=("imgs", k.n_images))

    def random_fft_block(n_feat):
        def block(data):
            X, y = data
            # Nondeterministic (fresh projection every run) — mirrors
            # KeystoneML's RandomFFT featurization; cannot be reused.
            rng = np.random.default_rng()
            W = rng.normal(0, 1.0, (X.shape[1] * X.shape[2], n_feat)
                           ).astype(np.float32)
            b = rng.uniform(0, 2 * np.pi, n_feat).astype(np.float32)
            Z = np.cos(X.reshape(len(X), -1).astype(np.float32) @ W + b)
            return Z, y
        return block

    if k.n_towers > 1:
        per_tower = k.n_features // k.n_towers
        logit_nodes = []
        for t in range(k.n_towers):
            z = wf.extractor(f"fftTower{t}", random_fft_block(per_tower),
                             [imgs], config=("fft", per_tower, t),
                             deterministic=False)
            head = wf.learner(
                f"towerHead{t}",
                lambda zy: train_softmax_np(zy[0], zy[1], k.reg, k.epochs),
                [z], config=("smnp", k.reg, k.epochs, t))
            logit_nodes.append(wf.learner(
                f"towerLogits{t}", lambda zy, w: zy[0] @ w,
                [z, head], config=("logits", t)))

        def ensemble_acc(data, *logit_blocks):
            _, y = data
            pred = np.argmax(np.sum(logit_blocks, axis=0), 1)
            return {"top1": float((pred == y).mean())}

        out = wf.reducer("evalAcc", ensemble_acc, [imgs] + logit_nodes,
                         config=("acc", k.eval_k, k.n_towers))
        wf.output(out)
        return wf

    feats = wf.extractor("randomFFT", random_fft_block(k.n_features),
                         [imgs], config=("fft", k.n_features),
                         deterministic=False)

    def train_softmax(data):
        Z, y = data
        Zj, yj = jnp.asarray(Z), jnp.asarray(y)
        W = jnp.zeros((Z.shape[1], 10))

        @jax.jit
        def step(W):
            logits = Zj @ W
            ce = -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(len(yj)), yj])
            return W - 0.5 * jax.grad(
                lambda W: ce + k.reg * jnp.sum(W * W))(W)

        # re-derive grad correctly (closure above must recompute ce)
        def loss(W):
            logits = Zj @ W
            ce = -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(len(yj)), yj])
            return ce + k.reg * jnp.sum(W * W)
        g = jax.jit(jax.grad(loss))
        for _ in range(k.epochs):
            W = W - 0.5 * g(W)
        return np.asarray(W)

    model = wf.learner("softmax", train_softmax, [feats],
                       config=("sm", k.reg, k.epochs))

    def acc(data, W):
        Z, y = data
        pred = np.argmax(Z @ W, 1)
        return {"top1": float((pred == y).mean())}

    out = wf.reducer("evalAcc", acc, [feats, model],
                     config=("acc", k.eval_k))
    wf.output(out)
    return wf


def mutate_mnist(k: MNISTKnobs, kind: str, rng) -> MNISTKnobs:
    if kind == "DPR":
        return dataclasses.replace(k, n_features=int(rng.choice(
            [256, 512, 768])))
    if kind == "LI":
        return dataclasses.replace(k, reg=float(rng.choice(
            [1e-4, 1e-3, 1e-2])), epochs=int(rng.choice([40, 60, 80])))
    return dataclasses.replace(k, eval_k=int(rng.integers(1, 5)))


# ---------------------------------------------------------------------------
# 5. LM training (a model-zoo config; large pytree materializations)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class LMKnobs:
    """LM training on a config from ``repro.configs``, cut to ``n_layers``.

    ``reduced`` swaps in the config's CPU-sized same-family widths
    (``configs.reduced``); ``reduced=False`` trains at published widths.
    Unlike the four survey workflows, the expensive reusable artifacts
    here are *pytrees of jax arrays* (a TrainState of params + AdamW
    moments), which is what the store's memory tier exists to serve
    zero-copy: a warm rerun should replay the trained state from host
    RAM without touching a single ``.npy``."""

    arch: str = "internlm2-1.8b"
    reduced: bool = True
    n_layers: int = 2
    seq_len: int = 64
    batch: int = 8
    steps: int = 4                # train batches (halving resource, LI)
    peak_lr: float = 1e-3
    seed: int = 0
    report_percentiles: bool = False   # PPR knob (loss-report formatting)


def lm_arch(k: LMKnobs) -> ArchConfig:
    # The config's attn_impl stays "chunked" (XLA): the Pallas flash
    # kernel is forward-only, and this workflow trains.
    cfg = configs.get(k.arch)
    if k.reduced:
        cfg = configs.reduced(cfg)
    return dataclasses.replace(cfg, num_layers=k.n_layers)


class _LMPrograms:
    """The LM workflow's jitted programs, built once per configuration and
    mesh (a fresh ``jax.jit`` per node call retraces), with the TrainState
    sharded on ``mesh`` under the config's train ruleset."""

    def __init__(self, cfg: ArchConfig, peak_lr: float, total_steps: int,
                 mesh):
        self.mesh = mesh
        self.state, self.batch_axes = shapes.train_shardings(cfg, mesh)
        self.init = jax.jit(
            functools.partial(train_steps.init_train_state, cfg),
            out_shardings=self.state)
        self.train = jax.jit(
            functools.partial(train_steps.train_step, cfg, peak_lr=peak_lr,
                              warmup_steps=2, total_steps=total_steps,
                              clip_norm=1.0),
            out_shardings=(self.state, None), donate_argnums=0)
        self.eval = jax.jit(lambda params, tokens: train_steps.loss_fn(
            cfg, params, {"tokens": tokens})[0])

    def put_tokens(self, tokens):
        return jax.device_put(tokens, shapes.batch_sharding(
            self.mesh, tokens.shape, self.batch_axes))

    def trained_placement(self):
        """``sharding_for_leaf`` for the train node's value
        ``{"losses": host array, "state": TrainState}``: the state's
        leaves onto their training placement, the losses left on host."""
        leaves = jax.tree_util.tree_leaves(
            {"losses": None, "state": self.state},
            is_leaf=lambda x: x is None)
        return lambda i, shape, dtype: leaves[i]


@functools.lru_cache(maxsize=16)
def _lm_programs(cfg: ArchConfig, peak_lr: float, total_steps: int,
                 mesh) -> _LMPrograms:
    return _LMPrograms(cfg, peak_lr, total_steps, mesh)


def build_lm(k: LMKnobs, mesh=None) -> Workflow:
    """tokens → initState → train → evalLoss. ``mesh`` (default: every
    local device, ``make_local_mesh()``) holds the TrainState and the
    batches; a stored TrainState reloads onto it."""
    cfg = lm_arch(k)
    pr = _lm_programs(cfg, k.peak_lr, max(k.steps, 3),
                      mesh if mesh is not None else make_local_mesh())
    wf = Workflow("lm")

    def make_tokens():
        rng = np.random.default_rng(k.seed + 101)
        # steps train batches + 1 held-out eval batch
        return rng.integers(0, cfg.vocab_size,
                            (k.steps + 1, k.batch, k.seq_len),
                            dtype=np.int32)

    tokens = wf.source("tokens", make_tokens,
                       config=("tok", cfg.vocab_size, k.seq_len, k.batch,
                               k.steps, k.seed))

    def init_state():
        with pr.mesh:
            return pr.init(jax.random.PRNGKey(k.seed))

    state0 = wf.source("initState", init_state, config=("init", cfg, k.seed))

    def train(tok, state):
        losses = []
        with pr.mesh:
            # A copy the steps may donate: the node's input stays whole
            # for the store's writer and the executor. Without donation,
            # a step would hold the input, its predecessor's output and
            # its own — three TrainStates.
            state = jax.device_put(state, pr.state, may_alias=False)
            for i in range(k.steps):
                state, metrics = pr.train(state, {"tokens":
                                                  pr.put_tokens(tok[i])})
                losses.append(float(metrics["loss"]))
        return {"state": state, "losses": np.asarray(losses, np.float64)}

    trained = wf.learner(
        "train", train, [tokens, state0],
        config=("train", cfg, k.seq_len, k.batch, k.steps, k.peak_lr))
    wf.load_shardings["train"] = pr.trained_placement()

    def eval_loss(tok, tr):
        params = tr["state"].params
        with pr.mesh:
            loss = pr.eval(params, pr.put_tokens(tok[-1]))
        leaves = jax.tree_util.tree_leaves(params)
        out = {"eval_loss": float(loss),
               "train_losses": tr["losses"].tolist(),
               # Where the evaluated state lives: a state reloaded onto
               # one device (or left on host) shows fewer devices than
               # the mesh holds.
               "placement": {
                   "min_devices": min(
                       len(x.sharding.device_set)
                       if isinstance(x, jax.Array) else 0 for x in leaves),
                   "split_leaves": sum(
                       isinstance(x, jax.Array)
                       and not x.sharding.is_fully_replicated
                       for x in leaves),
                   "leaves": len(leaves)}}
        if k.report_percentiles:
            qs = np.percentile(tr["losses"], [0, 50, 100])
            out["loss_percentiles"] = {"p0": float(qs[0]),
                                       "p50": float(qs[1]),
                                       "p100": float(qs[2])}
        return out

    out = wf.reducer("evalLoss", eval_loss, [tokens, trained],
                     config=("eval", k.report_percentiles))
    wf.output(out)
    return wf


def mutate_lm(k: LMKnobs, kind: str, rng) -> LMKnobs:
    if kind == "DPR":
        if rng.random() < 0.5:
            return dataclasses.replace(k, seq_len=int(rng.choice(
                [48, 64, 96])))
        return dataclasses.replace(k, batch=int(rng.choice([4, 8])))
    if kind == "LI":
        if rng.random() < 0.5:
            return dataclasses.replace(k, peak_lr=float(rng.choice(
                [3e-4, 1e-3, 3e-3])))
        return dataclasses.replace(k, steps=int(rng.choice([3, 4, 6])))
    return dataclasses.replace(
        k, report_percentiles=not k.report_percentiles)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class WorkflowDef:
    name: str
    knobs0: object
    build: Callable
    mutate: Callable
    freqs: dict     # DPR/LI/PPR iteration-type frequencies (survey [78])


WORKFLOWS = {
    "census": WorkflowDef("census", CensusKnobs(), build_census,
                          mutate_census,
                          {"DPR": 0.3, "LI": 0.2, "PPR": 0.5}),
    "genomics": WorkflowDef("genomics", GenomicsKnobs(), build_genomics,
                            mutate_genomics,
                            {"DPR": 0.2, "LI": 0.5, "PPR": 0.3}),
    "nlp": WorkflowDef("nlp", NLPKnobs(), build_nlp, mutate_nlp,
                       {"DPR": 1.0, "LI": 0.0, "PPR": 0.0}),
    "mnist": WorkflowDef("mnist", MNISTKnobs(), build_mnist, mutate_mnist,
                         {"DPR": 0.3, "LI": 0.4, "PPR": 0.3}),
    "lm": WorkflowDef("lm", LMKnobs(), build_lm, mutate_lm,
                      {"DPR": 0.3, "LI": 0.5, "PPR": 0.2}),
}


def iteration_schedule(wd: WorkflowDef, n_iters: int, seed: int
                       ) -> list[object]:
    """knobs for iterations 0..n-1 (0 = initial)."""
    rng = np.random.default_rng(seed)
    kinds = list(wd.freqs)
    probs = np.asarray([wd.freqs[x] for x in kinds])
    probs = probs / probs.sum()
    knobs = [wd.knobs0]
    cur = wd.knobs0
    for _ in range(n_iters - 1):
        kind = str(rng.choice(kinds, p=probs))
        nxt = wd.mutate(cur, kind, rng)
        tries = 0
        while nxt == cur and tries < 5:   # ensure an actual edit
            nxt = wd.mutate(cur, kind, rng)
            tries += 1
        knobs.append(nxt)
        cur = nxt
    return knobs
