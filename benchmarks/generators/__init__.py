"""Traffic generators, one module per `generator` kind a traffic file names."""
