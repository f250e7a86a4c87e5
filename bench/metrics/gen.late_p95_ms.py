"""How late the open-loop generator submitted, in ms: the 95th percentile
over the window's requests of submit time minus due time.  Moves
``ttft_p90_ms`` (a late generator delays first tokens)."""

import common


def read(run):
    if run["loop"] != "open" or not run["late_s"]:
        return None
    return 1e3 * common.percentile(run["late_s"], 95)
