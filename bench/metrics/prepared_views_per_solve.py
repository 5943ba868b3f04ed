"""prepared_views_per_solve: the tensor-sized matrix views cp_als built
for a solve (CPState.prepared_views: made once, before the first sweep),
averaged over the window's solves.  None where the program does not
count them."""


def read(run):
    views = [getattr(u.get("state"), "prepared_views", None) for u in run.units]
    if not views or None in views:
        return None
    return sum(views) / len(views)
