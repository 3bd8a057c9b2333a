"""Launchers: solve and serve drivers, the engine's mesh and dry run."""
