"""One runner per kind of job, found by the ``runner`` name in a workload file."""
