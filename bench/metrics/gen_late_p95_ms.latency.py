"""How late the load generator sent its requests, at the 95th percentile
(host clock): a generator that falls behind hides the server's tail."""

from bench.traffic.open_poisson import nearest_rank


def read(run):
    late = run.obs.get("late_s")
    if not late:
        return None
    return 1e3 * nearest_rank(late, 95)
