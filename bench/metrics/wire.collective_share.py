"""Share of the traced window each chip spends in collectives (all-reduce,
collective-permute and kin), averaged over the chips."""
PATTERN = r"all-reduce|collective-permute|all-gather|reduce-scatter|all-to-all"


def read(run):
    if run.trace is None or not run.trace.matching(PATTERN):
        return None
    return 100.0 * run.trace.time_s(PATTERN) / run.trace.window_s
