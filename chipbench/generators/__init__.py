"""Traffic generators, found by the ``generator`` name in a traffic file."""
