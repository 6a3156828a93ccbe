"""inbox: slices left in the deepest per-source queue after a tick's pop: mean
``inbox_backlog`` over the window's ticks on the node where it is deepest
(0 undisturbed; 1 or 2 once a late tick has left a standing backlog)."""

from benchmark.program_marks import worst_node_mean


def read(r):
    return worst_node_mean(r, "inbox_backlog")
