"""A by-row lookup into a node table, gather against select, alone on one
chip: seconds a call.

Run on the chip (`python tools/lookup_forms.py`) before a PR moves
`core._SELECT_MAX_ENTRIES`, the entry count up to which the boost scan
reads a node table by a select over its entries and past which by a
gather (the table this wrote is in PERF.md section 6), and before one
moves how the class batch reads a set table (`--tables sets --entries
1,2,4,8,16 --shape 23x4898816`: `gbm-kddcup99.train`'s levels), or
how its node pass sums the rows by node (`--tables sums --entries
63,1023,2047,4095 --shape 23x4898816`: depth 5, 9, 10 and 11). Each
(shape, entries, form) is jitted alone, run once to compile and
`--calls` times more, and the least seconds kept. The tables:

  four   the descent's four tables of a level (`feat`, `bin_` int32,
         `na_l`, `can` bool) behind one index, through
           gather4      the four gathers `t[idx]` (the grower's old
                        reading: under `vmap`, four batched gathers)
           select4      four selects (`core._node_lookup` held to the
                        select) that share the compare
           word_select  the SHIPPED reading (`core._split_of_rows`): the
                        four packed into one word, one select
           word_gather  the same word, one gather
  f32    one float32 table (the margin's leaf value), through
           gather       `t[idx]`
           select       `core._node_lookup` held to the select
  sets   a level's set tables (`Tree.left_bins`: [entries, 256] bool, a
         node's bins that go left) read at each row's (node, bin) — for
         this kind ``entries`` counts the nodes — through
           set_gather   the one-tree reading `core._goes_right` takes:
                        one gather from the flat [entries x 256] table
                        (under `vmap` a batched gather)
           set_select   `core._node_lookup` of the flat table held to
                        the select (entries x 256 compares a row)
           set_word     the SHIPPED reading under the class batch
                        (`core._set_word_lookup`): the bins packed 32 to
                        a word, a select over entries x 8 words, the
                        row's bit

  sums   not a lookup but its mirror: float32 values of every row
         summed into the node it rests at (`core._resting_sums`, the
         node pass of a multinomial GBM with sets: g, h and a count,
         ``entries`` the heap's nodes) — compared with the first form
         to float32's rounding (`max_rel_diff`), not bitwise — through
           sum_select   one fused compare-and-sum over [entries, rows]
           sum_segment  a segment sum (a scatter-add), the form past
                        `core._SELECT_MAX_ENTRIES`
         with ``KxROWS`` the K class trees' rows as the program holds
         them, [K, rows], with no `vmap`

A shape is `ROWS` (one tree: idx [rows], tables [entries]) or `KxROWS`
(the class batch: K trees under `vmap`, as `_boost_shard` grows them
for K > 1). Every form's result is compared with the gather's, bitwise.
`--limit` is the seconds after which a (form, shape) is abandoned (its
line says `timeout`).

One JSON line a measurement on stdout and in
`chiprun_out/lookup_forms.jsonl`; a last line gives `select_max`, the
largest entry count measured at which every select beat its gather in
every shape, and `select_loses_at`, the counts below it at which one
did not (at 32 and 64 entries on one tree, where the compiler already
turns the gather into a select of its own: PERF.md section 6). Exits non-zero without a TPU;
`tests/test_descent.py` holds the select to the gather on the CPU.
"""

import argparse
import contextlib
import json
import os
import signal
import sys
import time
import unittest.mock as mock

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from h2o_kubernetes_tpu.models.tree import core

ENTRIES = (32, 64, 127, 128, 256, 512, 1024, 2047, 2048, 8191)
SHAPES = ("4194304", "7x581632")
N_FEAT, N_BINS = 28, 256


def _held(form):
    """`core._SELECT_MAX_ENTRIES` for a form: every table a select, or
    every table a gather, read as the function is traced; the shipped
    rule for the shipped set reading."""
    if form == "set_word":
        return core._SELECT_MAX_ENTRIES
    return (1 << 30) if form.endswith("select") or form == "select4" \
        else 0


def _gather4(idx, feat, bin_, na_l, can):
    return feat[idx], bin_[idx], na_l[idx], can[idx]


def _select4(idx, feat, bin_, na_l, can):
    return tuple(core._node_lookup(t, idx) for t in (feat, bin_, na_l, can))


def _word(idx, feat, bin_, na_l, can):
    return core._split_of_rows(idx, feat, bin_, na_l, can, N_FEAT, N_BINS)


def _set_flat(idx, rowbin, lb):
    return core._node_lookup(lb.reshape(-1), idx * N_BINS + rowbin)


FORMS = {"four": {"gather4": _gather4, "select4": _select4,
                  "word_select": _word, "word_gather": _word},
         "f32": {"gather": lambda idx, t: t[idx],
                 "select": lambda idx, t: core._node_lookup(t, idx)},
         "sets": {"set_gather": lambda idx, rb, lb: ~core._goes_right(
                      rb, None, None, N_BINS, lb, idx),
                  "set_select": _set_flat,
                  "set_word": lambda idx, rb, lb: core._set_word_lookup(
                      lb, idx, rb)},
         "sums": {form: lambda idx, g, h, c: core._resting_sums(
                      idx, (g, h, c), N_SUMS[0])
                  for form in ("sum_select", "sum_segment")}}
FIRST = {"four": "gather4", "f32": "gather", "sets": "set_gather",
         "sums": "sum_select"}
N_SUMS = [0]           # the node count `sums` is traced with


def _case(kind, K, rows, entries, seed):
    """idx [K?, rows] in [0, entries) and the tables [K?, entries]."""
    ks = jax.random.split(jax.random.key(seed), 5)
    lead = (K,) if K else ()
    idx = jax.random.randint(ks[0], lead + (rows,), 0, entries, jnp.int32)
    e = lead + (entries,)
    if kind == "f32":
        t = jax.random.normal(ks[1], e, jnp.float32)
        return idx, (t.at[..., 0].set(-0.0),)
    if kind == "sums":
        return idx, (jax.random.normal(ks[1], lead + (rows,)),
                     jax.random.uniform(ks[2], lead + (rows,)),
                     jnp.ones(lead + (rows,), jnp.float32))
    if kind == "sets":
        return idx, (jax.random.randint(ks[1], lead + (rows,), 0, N_BINS,
                                        jnp.int32),
                     jax.random.bernoulli(ks[2], 0.5, e + (N_BINS,)))
    return idx, (jax.random.randint(ks[1], e, 0, N_FEAT, jnp.int32),
                 jax.random.randint(ks[2], e, 0, N_BINS - 1, jnp.int32),
                 jax.random.bernoulli(ks[3], 0.5, e),
                 jax.random.bernoulli(ks[4], 0.9, e))


@contextlib.contextmanager
def _limit(seconds):
    """SIGALRM after ``seconds`` (0: never) of a (form, shape)."""
    def _raise(*_):
        raise TimeoutError(f"over {seconds} s")

    if seconds:
        signal.signal(signal.SIGALRM, _raise)
        signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)


def _same(a, b):
    return all(bool(jnp.array_equal(jax.lax.bitcast_convert_type(x, jnp.int32)
                                    if x.dtype == jnp.float32 else x,
                                    jax.lax.bitcast_convert_type(y, jnp.int32)
                                    if y.dtype == jnp.float32 else y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def measure(shape, kind, entries, calls, seed, limit=0):
    """One line a form at one (shape, entries); the gather first, so
    that the others are compared with it."""
    K, rows = (int(shape.split("x")[0]), int(shape.split("x")[1])) \
        if "x" in shape else (0, int(shape))
    idx, tables = _case(kind, K, rows, entries, seed)
    N_SUMS[0] = entries
    want = None
    first = FIRST[kind]
    for form in sorted(FORMS[kind], key=lambda f: f != first):
        line = {"shape": shape, "tables": kind, "entries": entries,
                "form": form}
        try:
            with _limit(limit), mock.patch.object(
                    core, "_SELECT_MAX_ENTRIES", _held(form)):
                one = FORMS[kind][form]
                # a function of its own a form: jit would share the
                # trace of `_word` between its two forms
                fn = jax.jit(jax.vmap(one) if K and kind != "sums"
                             else lambda *a: one(*a))
                t0 = time.perf_counter()
                got = jax.block_until_ready(fn(idx, *tables))
                line["first_s"] = time.perf_counter() - t0
                secs = []
                for _ in range(calls):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(idx, *tables))
                    secs.append(time.perf_counter() - t0)
            line["min_s"], line["max_s"] = min(secs), max(secs)
            if form == first:
                want = got
            elif want is not None and kind == "sums":
                line["max_rel_diff"] = float(jnp.max(
                    jnp.abs(got - want) / jnp.maximum(jnp.abs(want),
                                                      1.0)))
            elif want is not None:
                line["bitwise_gather"] = _same(got, want)
        except TimeoutError as e:
            line["timeout"] = str(e)
        except Exception as e:      # this form failed here; go on
            line["error"] = repr(e)[:400]
        yield line


def select_max(lines):
    """{"select_max": the largest entry count at which, in every shape
    and for both kinds of table, the select (the shipped word for the
    four) beat its gather, 0 where none did; "select_loses_at": the
    entry counts below it at which a select did not}."""
    best = {}
    for ln in lines:
        if "min_s" in ln:
            best[(ln["shape"], ln["tables"], ln["entries"],
                  ln["form"])] = ln["min_s"]
    pairs = {"four": ("word_select", "word_gather"),
             "f32": ("select", "gather")}
    wins = {}
    for (shape, kind, entries, form), s in best.items():
        if kind not in pairs:
            continue
        sel, gat = pairs[kind]
        if form == sel:
            other = best.get((shape, kind, entries, gat))
            wins.setdefault(entries, []).append(
                other is not None and s < other)
    won = [e for e in sorted(wins) if all(wins[e])]
    n = won[-1] if won else 0
    return {"select_max": n,
            "select_loses_at": [e for e in sorted(wins) if e < n
                                and not all(wins[e])]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shape", action="append", default=None,
                    help=f"ROWS or KxROWS; default {list(SHAPES)}")
    ap.add_argument("--entries", default=None,
                    help=f"comma-separated; default {ENTRIES}")
    ap.add_argument("--tables", default="four,f32")
    ap.add_argument("--calls", type=int, default=4)
    ap.add_argument("--seed", type=int, default=41)
    ap.add_argument("--limit", type=int, default=0,
                    help="seconds a (form, shape) may take, compile "
                         "included; 0: none")
    args = ap.parse_args(argv)
    entries = tuple(int(e) for e in args.entries.split(",")) \
        if args.entries else ENTRIES
    from h2o_kubernetes_tpu.runtime.backend import require_tpu

    require_tpu("lookup_forms")
    os.makedirs("chiprun_out", exist_ok=True)
    lines = []
    with open("chiprun_out/lookup_forms.jsonl", "a") as sink:
        def put(line):
            txt = json.dumps(line)
            print(txt, flush=True)
            sink.write(txt + "\n")
            sink.flush()

        for shape in args.shape or SHAPES:
            for kind in args.tables.split(","):
                for e in entries:
                    for line in measure(shape, kind, e, args.calls,
                                        args.seed, args.limit):
                        lines.append(line)
                        put(line)
        put(select_max(lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
