"""What a TPC-H SF 1 cell asks of the system under test beyond its builds
(`harness.Run.create_index`): the rows a build indexes, and a cold start of
the host caches before each build."""


def source_rows(run, index: str) -> int:
    table = run.cfg["indexes"][index]["table"]
    return len(next(iter(run.data[table].values())))


def drop_host_caches() -> None:
    """Forget every decoded source file, so the next build decodes the
    source as a user's first build does."""
    from hyperspace_tpu.engine import scan_cache

    for cache in (
        scan_cache.global_scan_cache(),
        scan_cache.global_bucketed_cache(),
        scan_cache.global_concat_cache(),
        scan_cache.global_filtered_cache(),
    ):
        cache.clear()
