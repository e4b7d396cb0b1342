"""Share of the window's wall between the device blocks of a fit: tree
read-back, the budget check and its margin download."""


def read(run):
    between = 0
    for s in run["served"]:
        b = s["blocks"]
        if len(b) < 2:
            continue
        between += (b[-1]["end_ns"] - b[0]["start_ns"]
                    - sum(x["end_ns"] - x["start_ns"] for x in b))
    if not any(len(s["blocks"]) >= 2 for s in run["served"]):
        return None
    return 100.0 * between / 1e9 / run["wall_s"]
