"""Quickstart: find the densest subgraph of a graph three ways.

    PYTHONPATH=src python examples/quickstart.py [path/to/snap_edgelist.txt]

With no argument, runs on a synthetic planted-dense-subgraph instance whose
optimum is known. With a SNAP .txt edge list (e.g. ca-GrQc from the paper's
Table 1), reproduces the paper's density columns directly.
"""
import sys

sys.path.insert(0, "src")

from repro.core import cbds_p, charikar, exact_densest, pbahmani
from repro.graphs.generators import planted_dense
from repro.graphs.io import load_snap_edgelist


def main():
    if len(sys.argv) > 1:
        g = load_snap_edgelist(sys.argv[1])
        print(f"loaded {sys.argv[1]}: {g}")
    else:
        g, mask, rho_planted = planted_dense(5000, 80, seed=0)
        print(f"synthetic planted instance: {g} (planted block rho="
              f"{rho_planted:.3f})")

    rho_pb, mask_pb, passes = pbahmani(g, eps=0.05)
    print(f"P-Bahmani(eps=0.05): rho~ = {rho_pb:.4f}  "
          f"({passes} passes, |S|={int(mask_pb.sum())})")

    res = cbds_p(g)
    print(f"CBDS-P:              rho~ = {res['density']:.4f}  "
          f"(densest core k*={res['k_star']}, core rho={res['core_density']:.4f}, "
          f"+{res['n_legit']} legit vertices)")

    rho_ch, _ = charikar(g)
    print(f"Charikar (serial 2-approx baseline): rho~ = {rho_ch:.4f}")

    if g.n_nodes <= 20_000:
        rho_star, _ = exact_densest(g, lo=res["density"],
                                    hi=2 * res["density"] + 1)
        print(f"Exact (Goldberg flow): rho* = {rho_star:.4f}")
        print(f"  -> CBDS-P ratio rho*/rho~ = {rho_star / res['density']:.4f} "
              f"(paper Table 3 pattern: better than the 2-approx bound "
              f"{rho_star / 2:.4f})")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
