"""One reader per metric: ``<metric name>.py`` defines ``read(run)``."""
