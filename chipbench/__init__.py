"""The chip benchmark of the VFL system: see README.md."""
