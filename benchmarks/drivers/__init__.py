"""Drivers, one module per `driver` kind a traffic file names."""
