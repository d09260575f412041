CREATE TABLE persons (
  datetime TIMESTAMP,
  id BIGINT,
  name TEXT
) WITH (
  connector = 'single_file',
  path = '$input_dir/persons.json',
  format = 'json',
  type = 'source',
  event_time_field = 'datetime'
);
CREATE TABLE auctions (
  datetime TIMESTAMP,
  id BIGINT,
  seller BIGINT
) WITH (
  connector = 'single_file',
  path = '$input_dir/auctions.json',
  format = 'json',
  type = 'source',
  event_time_field = 'datetime'
);
CREATE TABLE new_sellers (
  id BIGINT,
  name TEXT,
  starttime TIMESTAMP,
  opened BIGINT
) WITH (
  connector = 'single_file',
  path = '$output_path',
  format = 'json',
  type = 'sink'
);
INSERT INTO new_sellers
SELECT P.id, P.name, P.window.start, A.opened
FROM (
  SELECT id, name, count(*) AS registered,
    tumble(interval '10 seconds') AS window
  FROM persons GROUP BY id, name, window
) AS P
JOIN (
  SELECT seller, count(*) AS opened,
    tumble(interval '10 seconds') AS window
  FROM auctions GROUP BY seller, window
) AS A
ON P.id = A.seller AND P.window = A.window;
