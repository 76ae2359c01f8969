"""Run modes, one module each, named by a traffic mix's ``mode`` key."""
