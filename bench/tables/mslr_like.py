"""MSLR-WEB30K's shape, made from ``--seed`` on the host.

The real table (Microsoft Research, Qin and Liu 2013, "Introducing LETOR
4.0 Datasets"; https://www.microsoft.com/en-us/research/project/mslr/)
has 136 numeric features a query-document row: 25 kinds for each of five
streams (body, anchor, title, URL, whole document), kind-major — covered
query terms and their ratio, stream length, IDF, sum / min / max / mean
/ variance of TF, of length-normalised TF and of TF·IDF, boolean model,
vector space model, BM25, LMIR.ABS, LMIR.DIR, LMIR.JM — then 11 document
features (slashes in the URL, URL length, inlinks, outlinks, PageRank,
SiteRank, two quality scores, query-URL clicks, URL clicks, dwell time),
a relevance label 0-4 and a `qid`, a query's rows contiguous. Fold1's
training split is 18,919 queries and 2,270,296 rows, 1 to 1,251 rows a
query, 120 on average.

This file keeps the columns' kinds and the queries' sizes, not the
rows: small integer counts, ratios k/terms, heavy-tailed lengths, 0/1
boolean-model columns, BM25 / LMIR floats, click counts with most rows
zero, an anchor stream empty for about half the documents. The label
is cut from a latent score — a query's own intercept, twenty-two of
the columns, noise — at about the published shares (51 / 33 / 13 / 2 /
1%, assumed). **The multiset of query sizes is fixed by (rows,
queries)**: the quantile function of a clipped log-normal, adjusted to
sum to the rows; the seed shuffles which query has which size, as one
real table has one set of sizes.

Made in fixed chunks of queries: a chunk's rows depend on (seed, chunk
index) and its queries' sizes alone."""

from __future__ import annotations

from statistics import NormalDist

import numpy as np

CHUNK_QUERIES = 1024
STREAMS = ("body", "anchor", "title", "url", "whole")
KINDS = ("covered_terms", "covered_ratio", "length", "idf",
         "tf_sum", "tf_min", "tf_max", "tf_mean", "tf_var",
         "ntf_sum", "ntf_min", "ntf_max", "ntf_mean", "ntf_var",
         "tfidf_sum", "tfidf_min", "tfidf_max", "tfidf_mean", "tfidf_var",
         "boolean", "vsm", "bm25", "lmir_abs", "lmir_dir", "lmir_jm")
DOC = ("url_slashes", "url_length", "inlinks", "outlinks", "pagerank",
       "siterank", "quality", "quality2", "query_url_clicks", "url_clicks",
       "dwell")
NAMES = tuple(f"{k}_{s}" for k in KINDS for s in STREAMS) + DOC
N_FEATURES = len(NAMES)                       # 136
MAX_QUERY, MEAN_SIGMA = 1251, 0.7             # sizes: clipped log-normal
# share of documents whose stream is not empty, and its median length
PRESENT = {"body": 0.98, "anchor": 0.5, "title": 0.97, "url": 1.0,
           "whole": 1.0}
MEDIAN_LEN = {"body": 600.0, "anchor": 12.0, "title": 8.0, "url": 9.0,
              "whole": 700.0}
# what the latent score of `_chunk` reads before it is standardised
LATENT_MEAN, LATENT_SD = -0.04, 2.03
# cuts of the latent score (a standard normal, nearly): 51/33/13/2/1 %
LABEL_CUTS = tuple(NormalDist().inv_cdf(p) for p in (0.51, 0.84, 0.97, 0.99))


def col(kind: str, stream: str) -> int:
    return KINDS.index(kind) * len(STREAMS) + STREAMS.index(stream)


def query_sizes(rows: int, queries: int) -> np.ndarray:
    """[queries] int64, ascending: the multiset of query sizes of a
    table of ``rows`` rows — the quantiles of a log-normal of mean
    rows / queries clipped to 1..1,251, the smallest 1, the largest
    1,251 where the rows allow, nudged to sum to ``rows``."""
    if not 1 <= queries <= rows <= queries * MAX_QUERY:
        raise ValueError(f"{rows} rows cannot make {queries} queries of "
                         f"1 to {MAX_QUERY}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / queries) for i in range(queries)])
    shape = np.exp(MEAN_SIGMA * z)

    def sizes_at(scale):
        s = np.clip(np.rint(scale * shape), 1, MAX_QUERY).astype(np.int64)
        if queries > 2:
            s[0] = 1
            if rows > 60 * queries:
                s[-1] = MAX_QUERY
        return s

    lo, hi = 1e-3, float(MAX_QUERY)
    for _ in range(60):
        mid = (lo + hi) / 2
        if sizes_at(mid).sum() < rows:
            lo = mid
        else:
            hi = mid
    s = sizes_at(hi)
    # the rest: a row off the middle queries, one each
    extra = int(s.sum() - rows)
    mid = np.argsort(np.abs(np.arange(queries) - queries // 2),
                     kind="stable")
    i = 0
    while extra:
        k = mid[i % queries]
        step = 1 if extra > 0 else -1
        if 0 < k < queries - 1 and 1 <= s[k] - step <= MAX_QUERY:
            s[k] -= step
            extra -= step
        i += 1
    return np.sort(s)


def _chunk(sizes: np.ndarray, seed: int, chunk: int):
    """One chunk's rows: (X [136, n] float32, y [n] int8)."""
    rng = np.random.default_rng([int(seed), chunk])
    Q, n = len(sizes), int(sizes.sum())
    q = np.repeat(np.arange(Q), sizes)
    X = np.zeros((N_FEATURES, n), dtype=np.float32)
    terms = rng.integers(1, 7, Q)[q]                      # query terms
    intercept = rng.normal(0.0, 0.55, Q)[q]
    latent = intercept.copy()
    for s in STREAMS:
        there = rng.random(n) < PRESENT[s]
        length = np.where(there, np.ceil(
            MEDIAN_LEN[s] * np.exp(rng.normal(0.0, 0.9, n))), 0.0)
        idf = rng.gamma(4.0, 1.5, Q)[q] * terms           # the query's
        match = rng.beta(1.2, 1.6, n)         # how well the stream matches
        k = np.where(there, rng.binomial(terms, match), 0)
        mean_tf = np.where(k > 0, 1.0 + rng.poisson(
            0.02 * np.minimum(length, 400.0) * match + 0.3), 0.0)
        tf_sum = k * mean_tf
        tf_max = np.where(k > 0, np.ceil(mean_tf * (1 + rng.random(n))), 0)
        tf_min = np.where(k == terms, np.floor(mean_tf * rng.random(n)), 0)
        tf_mean = tf_sum / terms
        tf_var = (tf_max - tf_mean) * (tf_mean - tf_min) * 0.5
        norm = 1.0 / np.maximum(length, 1.0)
        X[col("covered_terms", s)] = k
        X[col("covered_ratio", s)] = k / terms
        X[col("length", s)] = length
        X[col("idf", s)] = idf
        for name, v in (("sum", tf_sum), ("min", tf_min), ("max", tf_max),
                        ("mean", tf_mean), ("var", tf_var)):
            X[col("tf_" + name, s)] = v
            X[col("ntf_" + name, s)] = v * (norm ** 2 if name == "var"
                                            else norm)
            X[col("tfidf_" + name, s)] = v * (
                (idf / terms) ** 2 if name == "var" else idf / terms)
        X[col("boolean", s)] = k == terms
        vsm = np.where(k > 0, np.clip(
            0.15 + 0.6 * match * k / terms + rng.normal(0, 0.08, n), 0, 1),
            0.0)
        bm25 = np.where(k > 0, idf / terms * k * mean_tf * 2.2 / (
            mean_tf + 1.2 * (0.25 + 0.75 * length / MEDIAN_LEN[s]))
            + rng.normal(0, 0.4, n), 0.0)
        lm = np.where(there, -6.0 * terms + 4.5 * k * np.log1p(mean_tf)
                      - 0.6 * np.log1p(length), -9.0 * terms)
        X[col("vsm", s)] = vsm
        X[col("bm25", s)] = bm25
        X[col("lmir_abs", s)] = lm + rng.normal(0, 1.0, n)
        X[col("lmir_dir", s)] = lm * 0.9 + rng.normal(0, 1.0, n)
        X[col("lmir_jm", s)] = lm * 1.1 + rng.normal(0, 1.5, n)
        # four columns a stream carry the label: coverage, BM25, the
        # vector space model and one language model, each standardised
        # by what it reads on this table
        w = {"body": 1.0, "anchor": 0.8, "title": 0.9, "url": 0.4,
             "whole": 0.7}[s]
        latent += w * (0.45 * (k / terms - 0.35) / 0.33
                       + 0.35 * np.tanh(bm25 / 6.0 - 0.6)
                       + 0.30 * (vsm - 0.3) / 0.2
                       + 0.25 * np.tanh((lm + 14.0) / 9.0))
    d = N_FEATURES - len(DOC)
    url_len = np.ceil(20 + rng.gamma(2.0, 18.0, n))
    pagerank = rng.gamma(1.5, 1.2, n)
    clicks = np.where(rng.random(n) < 0.15, rng.geometric(0.2, n), 0)
    url_clicks = np.where(rng.random(n) < 0.35,
                          np.ceil(rng.pareto(1.1, n) * 3), 0)
    X[d + 0] = rng.poisson(url_len / 25.0)
    X[d + 1] = url_len
    X[d + 2] = np.floor(rng.pareto(0.9, n) * 4)
    X[d + 3] = rng.poisson(25.0, n)
    X[d + 4] = pagerank
    X[d + 5] = np.floor(rng.gamma(1.2, 40.0, n))
    X[d + 6] = rng.integers(0, 256, n)
    X[d + 7] = rng.integers(0, 256, n)
    X[d + 8] = clicks
    X[d + 9] = np.minimum(url_clicks, 1e6)
    X[d + 10] = np.where(url_clicks > 0, rng.gamma(2.0, 30.0, n), 0.0)
    latent += 0.35 * np.tanh(pagerank / 1.8 - 1.0) \
        + 0.9 * np.minimum(clicks, 8) / 8.0
    # to a standard normal, about, with a noise term a third of it
    latent = (latent - LATENT_MEAN) / LATENT_SD * 0.94 \
        + rng.normal(0, 0.33, n)
    y = np.searchsorted(np.array(LABEL_CUTS), latent).astype(np.int8)
    return X, y


def mslr_like(rows: int, queries: int, seed: int):
    """(X [136, rows] float32, one contiguous row per column; y [rows]
    int8 relevance 0-4; qid [rows] int32 ascending, a query's rows
    contiguous). The same (rows, queries, seed) gives the same table;
    every seed of one (rows, queries) has the same multiset of query
    sizes."""
    sizes = np.random.default_rng([int(seed), 0x515E]).permutation(
        query_sizes(rows, queries))
    X = np.empty((N_FEATURES, rows), dtype=np.float32)
    y = np.empty(rows, dtype=np.int8)
    lo = 0
    for c, a in enumerate(range(0, queries, CHUNK_QUERIES)):
        part = sizes[a:a + CHUNK_QUERIES]
        hi = lo + int(part.sum())
        X[:, lo:hi], y[lo:hi] = _chunk(part, seed, c)
        lo = hi
    qid = np.repeat(np.arange(queries, dtype=np.int32), sizes)
    return X, y, qid


def as_columns(X: np.ndarray, y: np.ndarray, qid: np.ndarray
               ) -> dict[str, np.ndarray]:
    """The table as a user hands it to `Frame.from_arrays`: 136 float32
    columns, the relevance `y` (float32 grades) and the `qid` column."""
    cols = {name: X[j] for j, name in enumerate(NAMES)}
    cols["y"] = y.astype(np.float32)
    cols["qid"] = qid
    return cols
