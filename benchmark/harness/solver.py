"""The program's solver configuration for a cell."""

from __future__ import annotations


def solver_config(run):
    """``SolverConfig`` from the configuration's ``map``, ``agents`` and
    ``solver`` entries; a control run applies the cell's ``control``."""
    from p2p_distributed_tswap_tpu.core.config import SolverConfig

    m = run.conf["map"]
    kw = dict(run.conf["solver"])
    if run.control:
        kw.update(run.work["control"])
    return SolverConfig(height=int(m["height"]), width=int(m["width"]),
                        num_agents=int(run.conf["agents"]), **kw)
