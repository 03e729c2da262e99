"""Share of semantic-cache lookups served from the cache, from
``SemanticCache.report()``."""


def read(run):
    if not run.cache or not run.cache["lookups"]:
        return None
    return 100.0 * run.cache["served"] / run.cache["lookups"]
