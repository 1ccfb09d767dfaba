"""The benchmark's own code: everything a later change to the program may not
touch. ``run.py`` at the top of this directory is the only entry point."""
