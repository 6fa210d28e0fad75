"""Drives the bundled `pr` program (pr.sp, float32 ranks).

Every run takes the traffic's parameters; the warm-up takes maxIter=1, so
the compiled program exits after one sweep through the whole loop body. A
run is checked by the L1 distance of its ranks from the float64
reference's, which stops by its own rule at the same beta.
"""
import ml_dtypes
import numpy as np

BUNDLED = "pr"
CONTROL_STORE = ml_dtypes.bfloat16      # the next precision below float32


def plan(edges: dict, traffic: dict, seed: int) -> dict:
    params = dict(traffic["params"])
    return {"inputs": [params], "warmup": dict(params, maxIter=1)}


def output(out: dict) -> dict:
    return {"rank": np.asarray(out["pageRank"]), "iterations": int(out["iterCount"])}


def reference(ref, edges: dict, ran: list, traffic: dict) -> dict:
    p = traffic["params"]
    return ref.pagerank(edges, p["delta"], p["beta"], p["maxIter"])


def compare(outputs: list, ran: list, want: dict, traffic: dict) -> dict:
    limit = traffic["limits"]["pr_l1_err"]
    errs = [float(np.abs(o["rank"].astype(np.float64) - want["rank"]).sum())
            for o in outputs]
    worst = max(errs, key=lambda e: (np.isnan(e), e)) if errs else float("nan")
    return {"numbers": {"pr_l1_err": worst},
            "ok_per_run": [e <= limit for e in errs],
            "info": {"iterations": sorted({o["iterations"] for o in outputs}),
                     "reference_iterations": want["iterations"],
                     "num_edges": want["num_edges"]}}


def work_bytes(n: int, e: int, want: dict, ran: list, traffic: dict) -> float:
    """Least bytes one run must move: per sweep, each in-edge's source id
    and contribution (8E), and per vertex its row pointer, out-degree, old
    and new rank (16N); as many sweeps as the float64 reference needs."""
    return float(want["iterations"] * (8 * e + 16 * n))


def bfloat16(ref, edges: dict, traffic: dict):
    """The reference with its ranks stored in bfloat16, the next precision
    below float32, returned as the program returns."""
    def run(**params):
        r = ref.pagerank(edges, params["delta"], params["beta"], params["maxIter"],
                         store=CONTROL_STORE)
        return {"pageRank": r["rank"].astype(np.float32), "iterCount": r["iterations"]}
    return run


CONTROLS = {"bfloat16": bfloat16}
