"""ORDER BY l_extendedprice DESC LIMIT 10: the ten values exactly; ties at
the cut may differ in which row shows, but each must be a lineitem row."""

from __future__ import annotations

import numpy as np

from . import as_bfloat16, unscaled

CONTROL = "bfloat16"  # float32 holds every l_extendedprice exactly


def reference(data):
    li = data["lineitem"]
    ext = li["l_extendedprice"]
    top = sorted((int(v) for v in ext[np.argpartition(ext, -10)[-10:]]),
                 reverse=True)
    cand = np.flatnonzero(ext >= top[-1])
    return top, {(int(li["l_orderkey"][i]), int(li["l_linenumber"][i]),
                  int(ext[i])) for i in cand}


def compare(rows, ref, fresh=None, key=None):
    top, members = ref
    got = [unscaled(r[2], 2) for r in rows]
    if got != top:
        return f"topn values: {got} != {top}"
    for r in rows:
        if (int(r[0]), int(r[1]), unscaled(r[2], 2)) not in members:
            return f"topn row {r} is not a lineitem row"
    return None


def _rows(li, idx) -> list[list[str]]:
    return [[str(int(li["l_orderkey"][i])), str(int(li["l_linenumber"][i])),
             f"{int(li['l_extendedprice'][i]) // 100}."
             f"{int(li['l_extendedprice'][i]) % 100:02d}"] for i in idx]


def render_exact(data, ref) -> list[list[str]]:
    li = data["lineitem"]
    ext = li["l_extendedprice"]
    cand = np.flatnonzero(ext >= ref[0][-1])
    return _rows(li, cand[np.argsort(-ext[cand], kind="stable")][:10])


def control_rows(data) -> list[list[str]]:
    """The control: the ten rows a sort on bfloat16 keys puts first. The
    keys tie in steps of 65536 cents near the top, so the rows it returns
    are lineitem rows, but not the ten dearest."""
    li = data["lineitem"]
    key = as_bfloat16(li["l_extendedprice"])
    return _rows(li, np.argsort(-key, kind="stable")[:10])
