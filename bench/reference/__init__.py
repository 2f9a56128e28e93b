"""Plain references, one module per `reference` named in a configuration."""
