"""Serving: median request wall of the closed-loop streams, client side,
ms.  With every stream always waiting, this is the number of streams over
the completed rate: the cell is judged on `rows_per_s`, and this number
swings with whether a serving window happened to fuse two queries."""
from tpubench.readers import percentile_ms


def read(run):
    return percentile_ms(run, 50)
