"""`ec8p4-12d-3dead`: the drives the configuration lists under
`dead_drives` die between the preload and the serial read-back, under
the live server, and stay dead to the stop (`server.Server.kill_drive`
says how a drive dies; run.py holds the run to `dead_drives_touched`
0 after the stop). `cell_notes`: what the program itself says of such
a node, for the result line's `cell.config_notes` — reported, not
compared; the series and the route are this deployment's, not the
harness's."""

from benchmark import readers

WINDOWS = "minio_tpu_get_kernel_windows_total"


def after_preload(srv, cfg, cli):
    del cli
    for d in cfg["dead_drives"]:
        srv.kill_drive(d)


def cell_notes(cfg, scrape_a, scrape_b, device):
    del cfg
    return {
        # the program's own view of a drive whose root is no directory
        "offline_by_the_program_at_t0": readers.series_sum(
            scrape_a, "minio_tpu_drives_offline"),
        # every window of the window's GETs has to be rebuilt
        # (`numpy`); another path says something was healed back
        "get_windows_by_path": {
            dict(k)["path"]: v - scrape_a.get(WINDOWS, {}).get(k, 0.0)
            for k, v in scrape_b.get(WINDOWS, {}).items()},
        # a loss pattern has a batcher of its own: what each one's
        # one-shot probe timed, device against host
        "probes_ms": {c["name"]: [c.get("device_ms"), c.get("host_ms")]
                      for c in device.get("calibration", [])
                      if c.get("route") == "reconstruct"}}
