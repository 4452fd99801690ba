"""99th percentile of how late the open-loop generator sent its requests
(sent minus due, on the host clock), in milliseconds.  A starved
generator shows here, not as a fast server."""

import numpy as np


def read(r):
    late = r.counters.get("gen_late_s")
    if late is None or len(late) == 0:
        return None
    return 1e3 * float(np.percentile(late, 99))
