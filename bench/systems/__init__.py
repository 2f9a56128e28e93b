"""Systems under test, one module per `system` named in a configuration."""
