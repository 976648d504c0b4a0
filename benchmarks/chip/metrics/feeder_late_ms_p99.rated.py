"""99th percentile, over the events due in the window, of how late the
feeder pushed each event after its scheduled arrival, in ms."""

import numpy as np


def read(ctx):
    late = ctx["feeder_late_s"]
    if late is None or not len(late):
        return None
    return 1e3 * float(np.percentile(late, 99, method="inverted_cdf"))
