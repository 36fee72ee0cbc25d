"""Most pages of the KV pool in use at once over the run, in percent of the
pool (engine.stats()["pages"])."""


def read(run):
    pages = run.stats.get("pages")
    if not pages or not pages.get("capacity"):
        return None
    return 100.0 * pages["high_water"] / pages["capacity"]
