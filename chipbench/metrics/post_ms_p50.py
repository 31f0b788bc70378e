"""HTTP front and store: median milliseconds of ``POST /jobs`` on the
client's clock (a store hit resolves inside it)."""
from chipbench.stats import nearest_rank


def read(rec):
    posts = [j["post_s"] for j in rec["window"].get("jobs", ())
             if "post_s" in j]
    return 1e3 * nearest_rank(posts, 0.5) if posts else None
