"""The benchmark's yardstick: cell loading, traffic generation, spans, the
trace reduction, the table of peaks and the roofline arithmetic. Later PRs
read these files and do not change them; what belongs to one configuration,
one traffic mix or one per-layer metric lives in a file of its own under
``configs/``, ``traffic/`` and ``layer_metrics/``."""
