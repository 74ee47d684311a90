"""Traffic drivers; a traffic file names its module under `driver`."""
