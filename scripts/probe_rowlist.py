"""What a row list and a gathered chunk cost on the chip: the probe that a
best-first pass over the rows of its nodes was sized from (PERF.md §6, PR 33).

    chiprun -- python scripts/probe_rowlist.py [--rows N] [--shares 0.02,0.1,0.4]
    python scripts/probe_rowlist.py --describe [--rows N]   # compile only, for a described v5e
    JAX_PLATFORMS=cpu python scripts/probe_rowlist.py --rows 40960 --allow-cpu   # rehearse

(a) the list of the rows where a mask holds, in row order, by method: one sort
    of a key that is the row where the mask holds and past every row where it
    does not; prefix sum + scatter; prefix sum + a network of log2(R) shifts
    (every listed row moves left by the unlisted rows before it, one bit of
    that distance a stage, lowest first: no two ever meet); ``jnp.nonzero``.
(b) the histogram of 32 built nodes (64 columns) over a list of n rows in a
    loop with a trip count known only on the device, 2,048 gathered rows a
    chunk through ``ops/histogram.py:_hist_chunk``, against the straight scan
    (``build_histogram_at``: ``lax.scan`` over the page as chunks) and the
    straight loop (the same loop, its chunks sliced from the page inside its
    body), with the page as the program holds it (rows in the lanes) and
    row-major (a row a line of lanes).
(c) whether the loop copies its accumulator every chunk: the ops of the
    compiled loop bodies that touch an ``f32[32,28,256,2]``.
(d) ``--only shipped``: what the library holds (``row_list``: the sort of
    entries that carry their row's node; ``build_histogram_listed``: the
    list's loop and the page's), the list and the scan apart, by share.

Times are host clock around a drained call, the least of ``--reps``.
"""
import argparse
import os
import re
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("--rows", type=int, default=10_500_096)
ap.add_argument("--shares", default="0.02,0.1,0.4")
ap.add_argument("--reps", type=int, default=4)
ap.add_argument("--only", default="list,scan,shipped")
ap.add_argument("--layouts", action="store_true",
                help="the listed scans over the page row-major and as int32 too")
ap.add_argument("--today", action="store_true",
                help="time build_histogram_at too (compiles for 5 min)")
ap.add_argument("--describe", action="store_true")
ap.add_argument("--allow-cpu", action="store_true")
args = ap.parse_args()
if args.describe:
    os.environ.update(JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled")
if args.describe or args.allow_cpu:
    os.environ["XTB_HIST_IMPL"] = "matmul"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from xgboost_tpu.ops import histogram  # noqa: E402
from xgboost_tpu.ops.histogram import _hist_chunk, build_histogram_at  # noqa: E402

R, F, B, T, N = args.rows, 28, 256, 2048, 32
SHARES = [float(s) for s in args.shares.split(",")]
i32 = jnp.int32


# ---- (a) the list ---------------------------------------------------------
def list_sort(mask):
    iota = jnp.arange(R, dtype=i32)
    return lax.sort(jnp.where(mask, iota, R), is_stable=False), jnp.sum(mask, dtype=i32)


def list_scatter(mask):
    iota = jnp.arange(R, dtype=i32)
    cs = jnp.cumsum(mask, dtype=i32)
    idx = jnp.zeros(R, i32).at[jnp.where(mask, cs - 1, R)].set(iota, mode="drop")
    return idx, cs[-1]


def list_shift(mask):
    iota = jnp.arange(R, dtype=i32)
    cs = jnp.cumsum(mask, dtype=i32)
    val = jnp.where(mask, iota, -1)
    d = iota - (cs - 1)  # how far left a listed row moves
    for k in range(max(R - 1, 1).bit_length()):
        s = 1 << k
        val_r = jnp.concatenate([val[s:], jnp.full(s, -1, i32)])
        d_r = jnp.concatenate([d[s:], jnp.zeros(s, i32)])
        take = (val_r >= 0) & (((d_r >> k) & 1) == 1)
        stay = (val >= 0) & (((d >> k) & 1) == 0)
        val = jnp.where(take, val_r, jnp.where(stay, val, -1))
        d = jnp.where(take, d_r, d)
    return val, cs[-1]


def list_nonzero(mask):
    return (jnp.nonzero(mask, size=R, fill_value=0)[0].astype(i32),
            jnp.sum(mask, dtype=i32))


def just_cumsum(mask):
    return jnp.cumsum(mask, dtype=i32), jnp.sum(mask, dtype=i32)


def just_chunk_sort(mask):
    """A sort inside each 2,048-row chunk: the first step of a list made
    chunk by chunk, here only for what it costs."""
    iota = jnp.arange(R, dtype=i32)
    return (lax.sort(jnp.where(mask, iota, R).reshape(R // T, T), dimension=1,
                     is_stable=False), jnp.sum(mask, dtype=i32))


LISTS = {"sort": list_sort, "scatter": list_scatter, "shift": list_shift,
         "nonzero": list_nonzero, "(cumsum alone)": just_cumsum,
         "(chunk sort alone)": just_chunk_sort}


# ---- (b) the scans --------------------------------------------------------
def zeros():
    return jnp.zeros((N, F, B, 2), jnp.float32)


def scan_listed(bins, gpair, pos, node0, idx, n):
    def body(i, acc):
        ix = lax.dynamic_slice(idx, (i * T,), (T,))
        ok = i * T + jnp.arange(T, dtype=i32) < n
        p = jnp.where(ok, pos[ix], -1)
        return acc + _hist_chunk(bins[ix], gpair[ix], p, node0, N, B, 2)

    return lax.fori_loop(0, (n + T - 1) // T, body, zeros())


def scan_listed_onegather(bins, gpair, pos, node0, idx, n):
    """The pair and the slot ride in the page's own gather: 28 + 3 columns of
    int32 would double the page, so here only to read what the two other
    gathers cost: the pair and slot of the first rows, not the listed ones."""
    def body(i, acc):
        ix = lax.dynamic_slice(idx, (i * T,), (T,))
        ok = i * T + jnp.arange(T, dtype=i32) < n
        g = lax.dynamic_slice(gpair, (i * T, 0), (T, 2))
        p = jnp.where(ok, lax.dynamic_slice(pos, (i * T,), (T,)), -1)
        return acc + _hist_chunk(bins[ix], g, p, node0, N, B, 2)

    return lax.fori_loop(0, (n + T - 1) // T, body, zeros())


def scan_listed_aux(bins, gpair, pos, node0, idx, n):
    """Two gathers a chunk: the pair and the slot as one (R, 3) array, made
    anew a call as a pass would make it."""
    aux = jnp.concatenate(
        [gpair, lax.bitcast_convert_type(pos, jnp.float32)[:, None]], axis=1)

    def body(i, acc):
        ix = lax.dynamic_slice(idx, (i * T,), (T,))
        ok = i * T + jnp.arange(T, dtype=i32) < n
        a = aux[ix]
        p = jnp.where(ok, lax.bitcast_convert_type(a[:, 2], i32), -1)
        return acc + _hist_chunk(bins[ix], a[:, :2], p, node0, N, B, 2)

    return lax.fori_loop(0, (n + T - 1) // T, body, zeros())


def scan_listed_key(bins, gpair, pos, node0, idx, n):
    """Two gathers a chunk: the list's entry holds its row's node above the
    row's 24 bits (made here from ``pos`` by a gather outside the loop, as
    the sort's key would hold it), so the slot is not gathered."""
    key = idx | ((pos[idx] - node0) << 24)

    def body(i, acc):
        k = lax.dynamic_slice(key, (i * T,), (T,))
        ok = i * T + jnp.arange(T, dtype=i32) < n
        ix = k & ((1 << 24) - 1)
        p = jnp.where(ok, node0 + (k >> 24), -1)
        return acc + _hist_chunk(bins[ix], gpair[ix], p, node0, N, B, 2)

    return lax.fori_loop(0, (n + T - 1) // T, body, zeros())


def scan_straight_loop(bins, gpair, pos, node0, chunks):
    def body(i, acc):
        b = lax.dynamic_slice(bins, (i * T, 0), (T, F))
        g = lax.dynamic_slice(gpair, (i * T, 0), (T, 2))
        p = lax.dynamic_slice(pos, (i * T,), (T,))
        return acc + _hist_chunk(b, g, p, node0, N, B, 2)

    return lax.fori_loop(0, chunks, body, zeros())


def scan_both(bins, gpair, pos, node0, idx, n, chunks):
    """The form a pass would hold: both loops, one of them with no trip."""
    return scan_listed(bins, gpair, pos, node0, idx, n) + scan_straight_loop(
        bins, gpair, pos, node0, chunks)


def scan_today(bins, gpair, pos, node0):
    return build_histogram_at.__wrapped__(bins, gpair, pos, node0, n_nodes=N,
                                          n_bin=B, stride=2)


def accumulator_ops(compiled):
    """(c): what the loop bodies do to an f32[N,F,B,2] besides the matmul."""
    comp, found = None, []
    for line in compiled.as_text().splitlines():
        if line.endswith("{") and not line.startswith(" "):
            comp = line.split()[0]
        elif (comp and ("region" in comp or "body" in comp)
              and re.search(r" = f32\[%d,%d,%d,2\]" % (N, F, B), line)
              and "} get-tuple-element(" not in line):
            found.append(re.sub(r", (metadata|backend_config)=.*$", "",
                                line.strip())[:150])
    return found


def scan_entries_ahead(bins, gpair, node0, rows):
    """The library's listed loop with the next chunk's gathers issued before
    this chunk's matmul (the gathered chunk rides in the loop's carry): does
    the compiler overlap the two?"""
    bits = histogram._row_bits(R)
    lane = jnp.arange(T, dtype=i32)

    def fetch(i):
        ok = i * T + lane < rows.n
        entry = lax.dynamic_slice(rows.entries, (jnp.minimum(i * T, R - T),), (T,))
        at = jnp.where(ok, entry & ((1 << bits) - 1), 0)
        return (bins.at[at].get(mode="promise_in_bounds"),
                gpair.at[at].get(mode="promise_in_bounds"),
                jnp.where(ok, node0 + 2 * (entry >> bits), -1))

    def body(i, carry):
        acc, (b, g, p) = carry
        ahead = fetch(i + 1)
        return acc + _hist_chunk(b, g, p, node0, N, B, 2), ahead

    return lax.fori_loop(0, -(-rows.n // T), body, (zeros(), fetch(0)))[0]


def scan_entries_packed(bins, gpair, node0, rows):
    """The library's listed loop over a page packed two columns to an int32
    (made anew a call, as a pass would): a row's gather moves 14 whole words
    and no half of a word.  The histogram's columns come out even ones
    first; put back here."""
    bits = histogram._row_bits(R)
    lane = jnp.arange(T, dtype=i32)
    packed = lax.bitcast_convert_type(bins.reshape(R, F // 2, 2), i32)

    def body(i, acc):
        ok = i * T + lane < rows.n
        entry = lax.dynamic_slice(rows.entries, (jnp.minimum(i * T, R - T),), (T,))
        at = jnp.where(ok, entry & ((1 << bits) - 1), 0)
        words = packed.at[at].get(mode="promise_in_bounds").T  # (F/2, T)
        cols = jnp.concatenate([words & 0xFFFF, words >> 16], axis=0)
        p = jnp.where(ok, node0 + 2 * (entry >> bits), -1)
        return acc + _hist_chunk(cols.T, gpair.at[at].get(mode="promise_in_bounds"),
                                 p, node0, N, B, 2)

    acc = lax.fori_loop(0, -(-rows.n // T), body, zeros())
    back = jnp.argsort(jnp.concatenate([jnp.arange(0, F, 2), jnp.arange(1, F, 2)]))
    return acc[:, back]


def shipped(bins, gpair, node0, u, node):
    """(d): the library's list and scan at the shares a pass may hold."""
    if not hasattr(histogram, "build_histogram_listed"):
        print("this checkout has no build_histogram_listed", flush=True)
        return
    make = jax.jit(lambda p, most: histogram.row_list(
        p, node0, n_nodes=N, stride=2, most=most))
    scan = jax.jit(lambda p, rows: histogram.build_histogram_listed(
        bins, gpair, p, node0, rows, n_nodes=N, n_bin=B, stride=2))
    for share in sorted(set(SHARES + [0.0, 0.2, 0.3])):
        # the listed rows at the built (even) slots, every other between two
        pos = node0 + jnp.where(u < share, 2 * node, 1)
        rows, left = make(pos, R), make(pos, -1)
        if share == 0.0:
            t = best(scan, pos, left)
            print(f"shipped: a list not written {best(make, pos, -1) * 1e3:.2f} ms; the "
                  f"page straight {t * 1e3:.2f} ms, {t / R * 1e9:.2f} ns a row", flush=True)
        t_list, t_scan = best(make, pos, R), best(scan, pos, rows)
        ref = scan(pos, left)
        print(f"shipped share {share}: the list {t_list * 1e3:.2f} ms, its scan "
              f"{t_scan * 1e3:.2f} ms, {t_scan / max(int(rows.n), 1) * 1e9:.2f} ns a listed "
              f"row of {int(rows.n)}; against the page's scan: max |diff| / max |ref| = "
              f"{float(jnp.max(jnp.abs(scan(pos, rows) - ref)) / jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30)):.2e}",
              flush=True)
        ahead = jax.jit(lambda r: scan_entries_ahead(bins, gpair, node0, r))
        t_ahead = best(ahead, rows)
        print(f"   with the next chunk's gathers issued ahead: {t_ahead * 1e3:.2f} ms, "
              f"{t_ahead / max(int(rows.n), 1) * 1e9:.2f} ns a listed row; equal to the "
              f"shipped scan: {bool(jnp.array_equal(ahead(rows), scan(pos, rows)))}", flush=True)
        packed = jax.jit(lambda r: scan_entries_packed(bins, gpair, node0, r))
        t_packed = best(packed, rows)
        print(f"   over a page packed two columns to an int32: {t_packed * 1e3:.2f} ms (the "
              f"packing in it), {t_packed / max(int(rows.n), 1) * 1e9:.2f} ns a listed row; "
              f"equal to the shipped scan: "
              f"{bool(jnp.array_equal(packed(rows), scan(pos, rows)))}", flush=True)


def other_layouts(bins):
    """The page row-major (a row a line of lanes) and widened to int32."""
    try:
        from jax.experimental.layout import Format, Layout

        rm = jax.block_until_ready(jax.device_put(
            bins, Format(Layout(major_to_minor=(0, 1)), bins.sharding)))
        print("row-major page:", rm.format, flush=True)
        return {"row-major": rm, "rows in the lanes, int32": bins.astype(i32)}
    except Exception as e:  # noqa: BLE001
        print("no row-major page:", str(e)[:300], flush=True)
        return {}


def best(fn, *a):
    jax.block_until_ready(fn(*a))
    ts = []
    for _ in range(args.reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*a))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def describe():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=chip)

    page = (shape((R, F), jnp.int16), shape((R, 2), jnp.float32),
            shape((R,), i32), shape((), i32))
    for name, fn in LISTS.items():
        t0 = time.perf_counter()
        jax.jit(fn).lower(shape((R,), bool)).compile()
        print(f"list {name}: compiles for a described v5e in "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
    for name, fn, more in (
            ("listed", scan_listed, (shape((R,), i32), shape((), i32))),
            ("straight loop", scan_straight_loop, (shape((), i32),)),
            ("both", scan_both, (shape((R,), i32), shape((), i32), shape((), i32))),
            ("today", scan_today, ()))[:4 if args.today else 3]:
        t0 = time.perf_counter()
        c = jax.jit(fn).lower(*page, *more).compile()
        print(f"scan {name}: compiles in {time.perf_counter() - t0:.1f} s; "
              f"temp {c.memory_analysis().temp_size_in_bytes} B; accumulator in "
              f"the loop: {accumulator_ops(c)}", flush=True)


def main():
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, "rows", R, flush=True)
    if dev.platform != "tpu" and not args.allow_cpu:
        sys.exit("no chip: a time from here says nothing (--allow-cpu rehearses)")
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    u = jax.random.uniform(k[0], (R,))
    if "list" in args.only:
        for share in SHARES:
            mask = u < share
            want = np.flatnonzero(np.asarray(mask))
            for name, fn in LISTS.items():
                try:
                    f = jax.jit(fn)
                    idx, n = f(mask)
                    ok = name.startswith("(") or (
                        int(n) == want.size
                        and np.array_equal(np.asarray(idx[:want.size]), want))
                    print(f"list share {share}: {name:<16} {best(f, mask) * 1e3:8.2f} ms"
                          f"  n {int(n)}  {'ok' if ok else 'WRONG'}", flush=True)
                except Exception as e:  # noqa: BLE001 - a probe reports and goes on
                    print(f"list share {share}: {name} failed: {str(e)[:300]}", flush=True)
    bins = jax.random.randint(k[1], (R, F), 0, B, i32).astype(jnp.int16)
    gpair = jnp.stack([jax.random.normal(k[2], (R,), jnp.float32),
                       jnp.full((R,), 0.25, jnp.float32)], 1)
    node0 = jnp.asarray(7, i32)
    if "shipped" in args.only:
        shipped(bins, gpair, node0, u, jax.random.randint(k[4], (R,), 0, N, i32))
    if "scan" not in args.only:
        return
    pos = node0 + jax.random.randint(k[3], (R,), 0, 2 * N, i32)
    chunks = R // T
    loop = jax.jit(scan_straight_loop)
    ref = loop(bins, gpair, pos, node0, jnp.asarray(chunks, i32))
    if args.today:
        today = jax.jit(scan_today)
        t_today = best(today, bins, gpair, pos, node0)
        print(f"scan today (lax.scan over the page as chunks): {t_today * 1e3:.2f} ms, "
              f"{t_today / R * 1e9:.2f} ns a row; against the straight loop: max |diff| "
              f"/ max |ref| =", float(jnp.max(jnp.abs(today(bins, gpair, pos, node0) - ref))
                                      / jnp.max(jnp.abs(ref))), flush=True)
    for c in (chunks, chunks // 2, 0):
        t = best(loop, bins, gpair, pos, node0, jnp.asarray(c, i32))
        print(f"scan straight loop, {c} chunks sliced in the body: {t * 1e3:.2f} ms"
              + (f", {t / (c * T) * 1e9:.2f} ns a row" if c else " (the loop's fixed cost)"),
              flush=True)
    print("   accumulator in the straight loop's body:",
          accumulator_ops(loop.lower(bins, gpair, pos, node0, jnp.asarray(0, i32)).compile()),
          flush=True)
    forms = {"listed (three gathers a chunk)": jax.jit(scan_listed),
             "listed (the page's gather alone)": jax.jit(scan_listed_onegather),
             "listed (pair and slot in one gather)": jax.jit(scan_listed_aux),
             "listed (the slot in the list's entry)": jax.jit(scan_listed_key)}
    pages = {"rows in the lanes (as held)": bins}
    if args.layouts:
        pages.update(other_layouts(bins))
    truth = {}
    for share in SHARES + [1.0]:
        idx = jnp.sort(jnp.where(u < share, jnp.arange(R, dtype=i32), R))
        n = jnp.sum(u < share, dtype=i32)
        idx = jnp.where(jnp.arange(R) < n, idx, 0)
        for pname, page in pages.items():
            for fname, f in forms.items():
                try:
                    t = best(f, page, gpair, pos, node0, idx, n)
                    got = f(page, gpair, pos, node0, idx, n)
                    same = ("" if "alone" in fname else " equal to the first form: " + str(
                        bool(jnp.array_equal(got, truth.setdefault(share, got)))))
                    print(f"scan share {share}: {fname}, page {pname}: {t * 1e3:.2f} ms, "
                          f"{t / max(int(n), 1) * 1e9:.2f} ns a listed row{same}", flush=True)
                except Exception as e:  # noqa: BLE001
                    print(f"scan share {share}: {fname}, page {pname} failed: {str(e)[:300]}",
                          flush=True)
        if share == 1.0:
            got = forms["listed (three gathers a chunk)"](bins, gpair, pos, node0, idx, n)
            print("   every row listed against the straight loop: max |diff| / max |ref| =",
                  float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref))), flush=True)
    f = forms["listed (three gathers a chunk)"]
    t = best(f, bins, gpair, pos, node0, idx, jnp.asarray(0, i32))
    print(f"scan of an empty list: {t * 1e3:.3f} ms", flush=True)
    print("   accumulator in the listed loop's body:",
          accumulator_ops(f.lower(bins, gpair, pos, node0, idx, n).compile()), flush=True)
    both = jax.jit(scan_both)
    idx = jnp.sort(jnp.where(u < 0.02, jnp.arange(R, dtype=i32), R))
    n = jnp.sum(u < 0.02, dtype=i32)
    idx = jnp.where(jnp.arange(R) < n, idx, 0)
    for nn, cc in ((n, 0), (0, chunks), (0, 0)):
        t = best(both, bins, gpair, pos, node0, idx, jnp.asarray(nn, i32), jnp.asarray(cc, i32))
        print(f"both loops in one program, n {int(nn)} chunks {cc}: {t * 1e3:.2f} ms", flush=True)


if __name__ == "__main__":
    describe() if args.describe else main()
