"""Share of profile-cache lookups in the window that hit: the change in
``repro.core.switching.profile_cache_info()`` hits over hits + misses,
summed over the answers."""


def read(run):
    n = run.profile_hits + run.profile_misses
    if n == 0:
        return None
    return 100.0 * run.profile_hits / n
