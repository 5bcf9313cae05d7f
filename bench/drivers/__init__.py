"""One window loop per operation kind, found by the traffic file's
``driver`` key: ``bench/drivers/<driver>.py``."""
