"""Generic runners, one per kind of traffic. A traffic file names its
runner under ``"runner"``; every other key of the file is a parameter
of that runner."""
