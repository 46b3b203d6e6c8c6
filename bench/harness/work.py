"""The work of one kernel call, from its shapes alone: each input read
once, each output written once.  It does not depend on how the call is
implemented."""
from __future__ import annotations

I32 = F32 = 4
BOOL = 1


def seg_waterfill_bytes(n_flows: int, n_links: int) -> int:
    """One flow allocation of ``n_flows`` flows over ``n_links`` links.

    Reads each flow's four path links (i32), its active flag (bool) and
    its Mathis bound (f32), and each link's capacity (f32); writes each
    flow's rate (f32) and each link's load (f32).
    """
    F, E = n_flows, n_links
    return F * (4 * I32 + BOOL + F32) + E * F32 + F * F32 + E * F32


def fw_minplus_bytes(n_nodes: int) -> int:
    """One all-pairs shortest-path closure of an ``n_nodes``-node graph:
    reads the f32 adjacency and writes the f32 distance matrix."""
    return 2 * n_nodes * n_nodes * F32
