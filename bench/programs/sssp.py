"""Drives the bundled `sssp` program (sssp.sp, int32 distances).

The roots are Graph500's search keys: `roots` distinct vertices with at
least one edge, drawn from the traffic's `root_seed` among the base
vertices and taken under the run's labelling. Every seed therefore runs
the same roots in the same order, and does the same work. The warm-up is
one whole run from the first root, so every branch of the compiled loop
has run before the window. A run is checked by the number of vertices
whose distance differs from the float64 Dijkstra's: the comparison is
exact.
"""
import ml_dtypes
import numpy as np

BUNDLED = "sssp"
UNREACHABLE = 2 ** 30        # the program's distance for "no path"
INT16_MAX = np.iinfo(np.int16).max


def plan(edges: dict, traffic: dict, seed: int) -> dict:
    n, label = edges["n"], edges["label"]
    degree = np.bincount(edges["src"], minlength=n) + np.bincount(edges["dst"], minlength=n)
    keys = np.flatnonzero(degree[label] > 0)             # base ids with an edge
    base = np.random.default_rng(traffic["root_seed"]).choice(
        keys, size=traffic["roots"], replace=False)
    inputs = [{"src": int(label[b])} for b in base]
    return {"inputs": inputs, "warmup": inputs[0]}


def output(out: dict) -> dict:
    return {"dist": np.asarray(out["dist"])}


def reference(ref, edges: dict, ran: list, traffic: dict) -> dict:
    """Dijkstra from every root the window ran."""
    roots = sorted({p["src"] for p in ran})
    got = ref.distances(edges, roots)
    return {"dist": dict(zip(roots, got["dist"])), "num_edges": got["num_edges"],
            "reached_edges": dict(zip(roots, got["reached_edges"]))}


def compare(outputs: list, ran: list, want: dict, traffic: dict) -> dict:
    wrong, reached, longest = [], [], 0.0
    for o, p in zip(outputs, ran):
        d = want["dist"][p["src"]]
        expect = np.where(np.isinf(d), UNREACHABLE, d)
        wrong.append(int((o["dist"].astype(np.float64) != expect).sum()))
        finite = d[np.isfinite(d)]
        reached.append(len(finite))
        longest = max(longest, float(finite.max()))
    limit = traffic["limits"]["sssp_mismatch"]
    return {"numbers": {"sssp_mismatch": max(wrong) if wrong else -1},
            "ok_per_run": [k <= limit for k in wrong],
            "info": {"reached": reached, "max_dist": longest,
                     "num_edges": want["num_edges"]}}


def work_bytes(n: int, e: int, want: dict, ran: list, traffic: dict) -> float:
    """Least bytes one run must move, the mean over the window's runs: each
    edge out of a reached vertex, its target, weight and source distance
    once (12 bytes), and each vertex's distance written and read once
    (8N)."""
    reached = [want["reached_edges"][p["src"]] for p in ran]
    return float(12 * np.mean(reached) + 8 * n)


def _as_program(d):
    return np.where(np.isinf(d), UNREACHABLE, d).astype(np.int64)


def bfloat16(ref, edges: dict, traffic: dict):
    """The reference's distances stored in bfloat16, the tempting 16-bit
    step below int32."""
    def run(src):
        d = ref.distances(edges, [src])["dist"][0]
        return {"dist": _as_program(d.astype(ml_dtypes.bfloat16).astype(np.float64))}
    return run


def int16(ref, edges: dict, traffic: dict):
    """The reference's distances held in int16, the next integer type below
    int32: a distance past its range reads as the type's "no path", which
    the program's answer is then given as."""
    def run(src):
        d = ref.distances(edges, [src])["dist"][0]
        return {"dist": _as_program(np.where(d < INT16_MAX, d, np.inf))}
    return run


def stopped_short(ref, edges: dict, traffic: dict):
    """The guarantee broken: the fixed point of exact distances, by a
    reference that stops one superstep early."""
    return lambda src: {"dist": _as_program(ref.stopped_short(edges, src)["dist"])}


CONTROLS = {"bfloat16": bfloat16, "int16": int16, "stopped_short": stopped_short}
