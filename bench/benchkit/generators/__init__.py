"""General generators, each found by the ``generator`` key of a traffic file."""
