"""One module per kind of configuration, named by its ``runner`` key."""
